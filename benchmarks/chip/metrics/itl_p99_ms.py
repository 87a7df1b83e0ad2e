"""99th percentile of every gap between successive tokens of one request, as
received on the host, over the requests due in the window. A few per cent
of the gaps carry a prefill chunk beside the decode step, so this is the
stall a chunk puts in a stream."""

from chipbench.stats import percentile
from chipbench.windows import window_gaps


def read(run):
    return percentile(window_gaps(run)[0], 99)
