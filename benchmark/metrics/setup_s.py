"""setup_s: every second of the run that is not the measured window:
data generation, process start, compile or cache load, warm-up steps,
the worker's drain and the reference check."""


def read(run):
    return run["setup_s"]
