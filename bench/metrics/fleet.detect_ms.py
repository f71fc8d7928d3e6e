"""From the SIGKILL of the worker the mix loses to the start of the
frontend's ``recover_worker`` span: how long the router took to see the
loss.  Nothing to read where no worker was lost, or none recovered."""


def read(ctx):
    w = ctx.window
    if getattr(w, "kill_t", None) is None or w.recover is None:
        return None
    return 1e3 * (w.recover[0] - w.kill_t)
