"""Share of the traced stretch in which the device runs nothing while a
``model.put`` span is open: the host turning the batch into a float32
device array and the transfer to the device (when spans are recorded
the put waits for the array to land).  The program's spans put on the
device trace's clock."""
from bench.lib import spans


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.ops or not getattr(tr, "spans", None):
        return None
    return 100.0 * spans.idle_while(tr, "model.put") / (tr.window_s * 1e9)
