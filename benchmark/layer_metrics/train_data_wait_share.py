"""Share of the trainer's step time spent waiting for input, over the rounds
the window closed: the step profiler's `data_wait` phase (`prepare_batch`'s
host-to-device put) plus the time the loop itself waited on the host
iterator's `next()`, which the profiler only clocks for a Ray Data shard."""


def read(collected):
    train = collected["train"]
    waited = sum(r["phases"]["data_wait"] for r in train["rounds"])
    waited += train["iterator_wait_s"]
    return 100.0 * waited / sum(r["duration_s"] for r in train["rounds"])
