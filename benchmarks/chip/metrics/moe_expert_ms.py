"""Device milliseconds per local update of the ops under the program's
``moe.experts`` scope, forward, backward and rematerialised forward alike
(``op_scopes.scope_ms``): MoE routed experts: the gather of the held pairs'
rows, the grouped products over the held experts and the gate-weighted
scatter-add."""
import op_scopes


def read(ctx):
    return op_scopes.scope_ms(ctx, "moe.experts")
