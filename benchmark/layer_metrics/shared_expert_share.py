"""Share of the device's busy time under ``veles.shared``, in percent:
the shared expert of the expert layers, a dense SwiGLU every token
passes, forward and backward (``reduce/deltascopes.py``). It lies inside
``moe_share`` and outside ``expert_matmul_roofline`` and
``expert_route_share``, which read the routed experts alone."""

from benchmark.reduce import deltascopes


def read(ctx):
    return deltascopes.share_percent(ctx, lambda op: op.sub == "shared")
