"""Process start to the first timed operation: imports, device, data, warm-up, compiles."""


def read(run):
    return run.setup_s
