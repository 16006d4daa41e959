"""Share (%) of the window in which the writer held entries the store
had refused, by the benchmark's clock."""


def read(run):
    t0, t1 = run.window
    stalled = sum(max(0.0, min(e, t1) - max(s, t0))
                  for s, e in run.phase.stalls)
    return 100.0 * stalled / (t1 - t0)
