"""Device milliseconds per local update of the ops under the program's ``mla``
scope, forward, backward and rematerialised forward alike
(``op_scopes.scope_ms``): attention (MLA): the q, kv_a, kv_b and o
projections, RoPE, the scores, softmax and weighted sum."""
import op_scopes


def read(ctx):
    return op_scopes.scope_ms(ctx, "mla")
