"""Engine step programs: the least time the chip could take for the decode
steps of the traced window (weights plus the valid KV rows of the active
lanes, or their FLOPs, whichever bounds), over the device time of the
``decode_step`` program (%)."""

from chipbench.engine_calls import inside, model_calls
from chipbench.flops import roofline_s


def read(run):
    if run.trace is None or run.trace_window is None:
        return None
    dev = run.trace.programs.get("decode_step", 0.0)
    calls = [c for c in inside(model_calls(run), *run.trace_window)
             if c.kind == "decode"]
    if dev <= 0 or not calls:
        return None
    need = sum(roofline_s(c.flops, c.nbytes, run.peaks) for c in calls)
    return 100.0 * need / dev
