"""Adapters: the one place per configuration that calls the program's
constructors."""


def require_same_tree(what: str, mine, theirs):
    """The benchmark's seeded tree has to have the shapes and types the
    program's ``init()`` would build (read with ``jax.eval_shape``): a
    program whose parameters moved is refused, not mis-fed."""
    import jax

    a = jax.tree_util.tree_map(lambda x: (x.shape, str(x.dtype)), mine)
    b = jax.tree_util.tree_map(lambda x: (x.shape, str(x.dtype)), theirs)
    if a == b:
        return
    for k in sorted(set(a) | set(b)):
        if a.get(k) != b.get(k):
            raise RuntimeError(
                f"the program's {what} tree differs from the "
                f"configuration's at {k}: benchmark {a.get(k)} vs program "
                f"{b.get(k)}")
    raise RuntimeError(f"the program's {what} tree has the same keys in "
                       f"another order")
