"""Set-up seconds: process start to window start (weights, engine or
trainer, compilation, warm-up shapes and warm-up traffic)."""


def read(run):
    return run.setup_s
