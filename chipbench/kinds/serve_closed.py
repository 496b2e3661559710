"""Kind ``serve_closed``: a closed loop against one deployed configuration.

``clients`` callers each send their next request when the last was answered,
from the ramp's start to the window's end; what is in flight when the window
ends is cut.  The end-to-end metric is the tokens served in the window:
a request's prompt tokens count when its first token arrives (its prefill is
then done) and each generated token when it arrives.
"""

from chipbench import serving


def run(cell, args) -> dict:
    if cell.traffic["loop"] != "closed":
        raise serving.BenchError("kind serve_closed needs a traffic file "
                                 "with loop 'closed'")
    return serving.run_cell(cell, args)


correct = serving.serving_correct
compared = serving.compared
device = serving.device_block
