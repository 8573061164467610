"""setup_s: seconds from the first input drawn to the window's start:
graph generation, validation, hierarchy set-up and warm-up solves, and in a
run that compiles, compilation."""


def read(record):
    return record["setup_s"]
