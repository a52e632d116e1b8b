"""Milliseconds a step the host itself needs: the time of the ``edl.step``
spans less what they hold of ``edl.data_wait`` (waiting for the reader)
and ``edl.loss_sync`` (waiting for the device), over the steps.  What is
left is batch preparation, dispatch, the progress report and the loop's
own code; 1 / it is the rate at which this host could feed a chip that
took no time.  Waits under 100 us are not in the source and stay in."""

from benchmark.lib import spans


def read(run):
    s = spans.with_steps(run)
    if s is None:
        return None
    own = sum(step.end - step.start for step in s.step_spans) - sum(
        s.inside_ns(name, spans.STEP)
        for name in ("edl.data_wait", "edl.loss_sync"))
    return own / 1e6 / s.steps
