"""Whole step: model FLOPs of all the work done in the window over the
window times the chip's peak (%). Serving counts every decode and prefill
call that ran inside the window; training counts every step started in it
(forward and backward, recomputation not counted), over the window that
closes when the last of them completes."""

from chipbench.engine_calls import inside, model_calls


def read(run):
    t0, t1 = run.window
    if run.train_steps:
        f = sum(fl for ts, te, tok, fl, loss in run.train_steps)
    elif run.engine_events is not None:
        f = sum(c.flops for c in inside(model_calls(run), t0, t1))
    else:
        return None
    return 100.0 * f / ((t1 - t0) * run.peaks["flops_per_s"])
