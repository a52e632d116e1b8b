"""Share of the traced stretch the training thread spent in
``edl.loss_sync``: the loss fetch at the log cadence and the fence at each
task's last step.  Near 100% the device sets the pace and the host is
hidden behind it.  (``loop.loss_sync_ms_per_step`` is the same phase from
the program's end-of-run totals, over the whole run.)"""

from benchmark.lib import spans


def read(run):
    return spans.share(run, "edl.loss_sync")
