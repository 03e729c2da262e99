"""Process start to the first offered request, compilation included."""


def read(run):
    return run.setup_s
