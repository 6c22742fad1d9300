"""Device-idle milliseconds per traced round while the host is in the
local-update dispatch (``FFTRunner.run_local``): the self time of the
program's ``phase.local_update`` spans (``spans.py``)."""
import spans


def read(ctx):
    return spans.layer_idle_ms(ctx, "local_update")
