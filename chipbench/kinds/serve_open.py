"""Kind ``serve_open``: an open loop against one deployed configuration.

Independent users: requests are sent on the traffic file's schedule whether
or not earlier ones have finished, after a ramp of the same traffic; every
request due in the window is waited for (up to ``drain_s``) and timed from
when it was due.  The end-to-end metrics are the tails over those requests.
"""

from chipbench import serving


def run(cell, args) -> dict:
    if cell.traffic["loop"] != "open":
        raise serving.BenchError("kind serve_open needs a traffic file with "
                                 "loop 'open'")
    return serving.run_cell(cell, args)


correct = serving.serving_correct
compared = serving.compared
device = serving.device_block
