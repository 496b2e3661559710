"""``trace_write_s``: what the traced run's one capture cost the serving
process after its traced seconds: from their end to the ``.xplane.pb`` on
disk (the worker's clock; ``state.jax_profile``'s reply carries it as
``write_s``, and the replica's ``device_report()``, read after the drain,
carries the process's last capture as ``last_capture``).  The capture starts
at 0.4 of the window, so these seconds fall inside it.  None where the
program reports no such thing (the parent of the PR that added it) or the run
was not traced."""


def read(evidence):
    last = (evidence.get("report_after") or {}).get("last_capture")
    if not evidence.get("trace") or not isinstance(last, dict):
        return None
    return last.get("write_s")
