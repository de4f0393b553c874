"""Readers of the per-layer metrics K-EXAONE brings: the expert
layer's counters and grouped product, a pool a cache group, and the
decode kernel's floor with a window a layer. Each returns None where
the program has no such counter, span or operation (a commit before
this architecture ran), and the line then leaves the metric out."""

from __future__ import annotations

from chipbench import k_exaone_yardstick as ky
from chipbench import xtrace, yardstick


def _layers(rec):
    """(windows a layer kept, how many of them are sparse, experts
    held): the architecture as ``records["model"]`` states it."""
    m = rec["model"]
    n = m["num_hidden_layers"]
    windows = [w or None for w in m["sliding_windows"][:n]]
    sparse = sum(1 for t in m["mlp_layer_types"][:n] if t == "sparse")
    return windows, sparse, m["num_experts"]


def _expert_tokens(rec) -> list[float]:
    """Tokens given to each (sparse layer, held expert) pair in the
    window, pairs that got none included."""
    _, sparse, held = _layers(rec)
    got = [
        v for k, v in rec["counters"].items() if k.startswith("moe.tokens.")
    ]
    if not got:
        return []
    return got + [0.0] * (sparse * held - len(got))


def tokens_per_expert_mean(trace, rec, kind):
    tokens, steps = _expert_tokens(rec), rec["counters"].get("moe.steps")
    if not tokens or not steps:
        return None
    return sum(tokens) / len(tokens) / steps


def load_max_over_mean(trace, rec, kind):
    tokens = _expert_tokens(rec)
    if not tokens or not sum(tokens):
        return None
    return max(tokens) * len(tokens) / sum(tokens)


def _pool_peak_pct(rec, group: str):
    peak = rec["pool_peaks"].get(f"pages_in_use.{group}")
    pages = rec["stats"].get(f"pool_pages.{group}")
    if not peak or not pages:
        return None
    return 100.0 * peak / (pages - 1)  # page 0 is the trash page


def pool_peak_pct_full(trace, rec, kind):
    return _pool_peak_pct(rec, "full")


def pool_peak_pct_window(trace, rec, kind):
    return _pool_peak_pct(rec, "window")


def _traced(rec):
    """(index, tick) of the ticks inside the traced part of the window
    that decoded something."""
    tr = rec["trace"]
    return [
        (i, t) for i, t in enumerate(rec["ticks"])
        if tr["t0"] <= t[0] and t[1] <= tr["t1"] and t[2]
    ]


def _op_seconds(trace, name: str):
    if not trace or not trace.devices:
        return None
    return xtrace.op_seconds(trace.devices[0]).get(name)


def expert_product_roofline(trace, rec, kind):
    """The grouped product's floor in the traced ticks against the
    device time of ``gmm`` (the Pallas grouped matmul: three calls a
    sparse layer and step). The counters cover the whole window; the
    traced ticks take their share of them by decode steps, the closed
    loop being as full at the window's end as at its start. The floor
    counts live rows only, the kernel also routes what an idle row
    holds: the share errs low."""
    seconds, ticks = _op_seconds(trace, "gmm"), _traced(rec)
    c = rec["counters"]
    steps = c.get("moe.steps")
    if not seconds or not ticks or not steps:
        return None
    share = len(ticks) * rec["serving"]["chunk"] / steps
    m = rec["model"]
    flops, nbytes = ky.expert_product_cost(
        c["moe.assignments_held"], c["moe.experts_hit"],
        m["hidden_size"], m["moe_intermediate_size"], rec["itemsize"],
    )
    floor = yardstick.floor_seconds(flops * share, nbytes * share, kind)
    return 100.0 * floor / seconds


def paged_decode_grouped_roofline(trace, rec, kind):
    """Bytes the decode kernel had to move in the traced ticks, the
    full layer at each live row's context and each window layer at
    ``min(context, window)``, against the device time of
    ``_paged_impl``."""
    seconds, ticks = _op_seconds(trace, "_paged_impl"), _traced(rec)
    if not seconds or not ticks or "tick_contexts" not in rec:
        return None
    windows, _, _ = _layers(rec)
    s = rec["shape"]
    nbytes = 0
    for i, _ in ticks:
        for j in range(rec["serving"]["chunk"]):
            nbytes += ky.grouped_decode_bytes(
                rec["tick_contexts"][i], j, windows, s["heads"],
                s["kv_heads"], s["head_dim"], rec["itemsize"],
            )
    return 100.0 * yardstick.floor_seconds(0, nbytes, kind) / seconds
