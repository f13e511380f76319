"""Programs compiled and written to the persistent cache in this run, which a
warm run would have fetched: the program's
``fedml_programs_built_total{source="compiled"}``.  0 in a warm run; above 0
says the run's ``setup_s`` was a cold one."""


def counter_children(name):
    """A counter of the program's process registry as ``{label value:
    count}``; nothing where the program keeps no such counter."""
    from fedml_tpu.core.mlops import metrics

    m = metrics.REGISTRY.collect().get(name)
    if m is None:
        return None
    return {key[0]: child.value for key, child in m.children().items()}


def read(run):
    sources = counter_children("fedml_programs_built_total")
    return None if sources is None else sources.get("compiled", 0.0)
