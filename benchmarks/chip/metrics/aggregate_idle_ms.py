"""Device-idle milliseconds per traced round while the host aggregates
(``Strategy.aggregate``, ``StreamAccumulator``): the self time of the
program's ``phase.aggregate``, ``phase.compensatory``, ``phase.weight_solve``,
``phase.accumulate`` and ``phase.flush`` spans (``spans.py``)."""
import spans


def read(ctx):
    return spans.layer_idle_ms(ctx, "aggregate")
