"""Tokens of every step started in the window, over the window, which
closes when the last of those steps completes."""


def read(run):
    t0, t1 = run.window
    return sum(tok for ts, te, tok, f, loss in run.train_steps) / (t1 - t0)
