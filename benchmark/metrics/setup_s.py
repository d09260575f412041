"""Process start -> the measured window opens, compilation included."""


def read(run):
    return run["setup_s"]
