"""Device milliseconds per local update of the ops under the program's
``moe.route`` scope, forward, backward and rematerialised forward alike
(``op_scopes.scope_ms``): MoE routing: the router's product, softmax, top-k,
the sort of the (token, expert) pairs and the held experts' group sizes."""
import op_scopes


def read(ctx):
    return op_scopes.scope_ms(ctx, "moe.route")
