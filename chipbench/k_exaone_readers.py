"""Readers of the per-layer metrics K-EXAONE brings: the expert
layer's counters and grouped product, a pool a cache group, and the
decode kernel's floor with a window a layer. Each returns None where
the program has no such counter, span or operation (a commit before
this architecture ran), and the line then leaves the metric out."""

from __future__ import annotations

from chipbench import k_exaone_yardstick as ky
from chipbench import xtrace, yardstick
from chipbench.decode_runs import decode_runs, seconds_in


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


def _op_seconds(trace, name: str):
    if not trace or not trace.devices:
        return None
    return xtrace.op_seconds(trace.devices[0]).get(name)


def expert_product_roofline(trace, rec, kind):
    """The grouped product's floor in the decode runs the trace holds
    whole against the device time of ``gmm`` inside those runs (the
    Pallas grouped matmul: three calls a sparse layer and step; a
    prefill program's calls are no decode step's and are left out, as
    the counters leave them out). The counters cover the whole window;
    the runs take their share of them by decode steps, the closed loop
    being as full at the window's end as at its start. The floor counts
    live rows only, the kernel also routes what an idle row holds: the
    share errs low."""
    runs = decode_runs(trace, rec)
    seconds = seconds_in(trace, runs, ("gmm",)) if runs else None
    c = rec["counters"]
    steps = c.get("moe.steps")
    if not seconds or not steps:
        return None
    share = len(runs) * rec["serving"]["chunk"] / steps
    m = rec["model"]
    flops, nbytes = ky.expert_product_cost(
        c["moe.assignments_held"], c["moe.experts_hit"],
        m["hidden_size"], m["moe_intermediate_size"], rec["itemsize"],
    )
    floor = yardstick.floor_seconds(flops * share, nbytes * share, kind)
    return 100.0 * floor / seconds if floor else None


def paged_decode_grouped_roofline(trace, rec, kind):
    """Bytes the decode kernel had to move in the decode runs the trace
    holds whole, the full layer at each live row's context and each
    window layer at ``min(context, window)``, against the device time
    of ``_paged_impl`` inside those runs."""
    runs = decode_runs(trace, rec)
    seconds = seconds_in(trace, runs, ("_paged_impl",)) if runs else None
    if not seconds or "tick_contexts" not in rec:
        return None
    windows, _, _ = _layers(rec)
    s = rec["shape"]
    nbytes = 0
    for i, _, _ in runs:
        for j in range(rec["serving"]["chunk"]):
            nbytes += ky.grouped_decode_bytes(
                rec["tick_contexts"][i], j, windows, s["heads"],
                s["kv_heads"], s["head_dim"], rec["itemsize"],
            )
    if not nbytes:
        return None
    return 100.0 * yardstick.floor_seconds(0, nbytes, kind) / seconds
