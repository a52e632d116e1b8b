"""Milliseconds a step the loop spends in its ``loss_sync`` span (the loss
fetch at the log cadence and the fence at each task's last step): the
program's own ``Timing`` total over its own count of steps, as the worker
logs both at its end of run, so over the whole run and not the window."""


def read(run):
    ends = [e for e in run.log["ends"].values()
            if e.get("steps") and "loss_sync" in e["timing"]]
    if not ends:
        return None
    return 1e3 * sum(e["timing"]["loss_sync"] for e in ends) / sum(
        e["steps"] for e in ends)
