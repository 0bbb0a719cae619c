"""Process start to the window: JAX start-up, traffic generation,
prewarm (compiles or cache loads), the writers joining."""


def read(rec):
    return rec["setup_s"]
