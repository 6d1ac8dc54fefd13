def read(ctx, key: str):
    """A count taken when set-up ended (programs in the lane's jit caches,
    persistent-cache misses)."""
    return ctx["setup"].get(key)
