"""From the start of the frontend's ``recover_worker`` span to the first
token that a survivor produced for the last of the migrated streams to
get one: the stall a moved stream's client sees after the loss is found.
A migrated stream's first ``replayed`` tokens are the prefix recovered
from the lost worker; its token at that index is the survivor's first.
Nothing to read where no stream was moved and resumed."""


def read(ctx):
    w = ctx.window
    if getattr(w, "recover", None) is None:
        return None
    firsts = [w.streams[i].times[r] for i, _, r in w.migrate
              if len(w.streams[i].times) > r]
    if not firsts:
        return None
    return 1e3 * (max(firsts) - w.recover[0])
