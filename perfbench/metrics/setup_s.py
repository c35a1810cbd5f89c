"""Set-up time in s: from the start of the process to the start of the
window: imports, data synthesis and preprocessing, inputs, engine build,
compiling or loading every program from the cache, warm-up traffic
(host clock)."""


def read(run):
    return run.setup_s
