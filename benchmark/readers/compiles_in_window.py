"""Programs the window had to compile or load: the larger of JAX's own
compile requests inside the window and the engine's CompileRegistry misses
(shape keys first dispatched inside it). Should read 0."""


def read(ctx, metric):
    registry = (ctx["after"]["registry_misses"]
                - ctx["before"]["registry_misses"])
    return float(max(registry, ctx["compiles"]["requests"]))
