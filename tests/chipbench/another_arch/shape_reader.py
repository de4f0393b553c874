"""A reader of this architecture's own: it knows the architecture by
the builder's ``shape`` alone."""


def query_heads_per_kv_head(trace, rec, kind):
    shape = rec["shape"]
    return shape["heads"] / shape["kv_heads"]
