"""KV memory: the most KV blocks live at once in the window, as a share of
the pool (null block excluded), from each step's ``StepStats``: live = pool -
(admissible free + promised growth). Moves ``ttft_p90_ms``: a full pool
holds requests in the queue."""


def read(run):
    pool = run.conf["serving"]["num_blocks"] - 1
    live = [pool - (s.free_blocks + s.reserved_blocks) for s in run.win.stats]
    return 100.0 * max(live) / pool if live else None
