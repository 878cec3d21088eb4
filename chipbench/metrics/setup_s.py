"""setup_s: seconds from process start to the window's opening (JAX and TPU
start-up, traffic generation, warm-up from the compile cache)."""


def read(record):
    return record["setup_s"]
