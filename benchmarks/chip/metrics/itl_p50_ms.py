"""50th percentile of every gap between successive tokens of one request, as
received on the host, over the requests due in the window. Most gaps carry
no prefill, so this is the gap of a decode step as the client sees it."""

from chipbench.stats import percentile
from chipbench.windows import window_gaps


def read(run):
    return percentile(window_gaps(run)[0], 50)
