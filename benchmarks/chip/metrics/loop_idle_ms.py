"""Device-idle milliseconds per traced round in the round loop
(``fl/server/loops.py``): the self time of ``fl.round`` and of every other
``phase.*`` span, and idle under no program span (``spans.py``). With the
three other ``*_idle_ms`` metrics it sums to the window's idle per round."""
import spans


def read(ctx):
    return spans.layer_idle_ms(ctx, "loop")
