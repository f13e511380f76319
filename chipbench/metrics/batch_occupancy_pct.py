"""Share of the engine's batch slots in use, sampled by the generator at 4 Hz
through ``engine.stats()``, mean over the window."""


def read(run):
    s = run.rec.samples.get("occupancy")
    return None if not s else 100.0 * sum(s) / len(s)
