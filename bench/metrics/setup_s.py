"""Process start to window open: weights made on the device from the
seed, compilation or compile-cache loads, prefix registration, warm-up
and the traffic's lead-in."""


def read(run):
    return run.setup_s
