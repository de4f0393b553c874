"""Count whole-pool copies in a benchmark cell's compiled decode and
chunk-prefill programs.

    chiprun -- python3 scripts/step_chunk_copies.py <cell>      # on the chip
    JAX_PLATFORMS=cpu python3 scripts/step_chunk_copies.py <cell> --describe

Builds the cell's ``ContinuousBatcher`` as ``chipbench/lm_engine.py``
does (same model, slots and pool pages), lowers the batcher's own
``_step_chunk`` on ``ShapeDtypeStruct``s, compiles it, and counts the
``copy`` / ``copy-start`` operations of the pool's shape in
``compiled.as_text()`` by result layout, and those of the token and the
position table's shape; then does the same for every
chunked-prefill pass the cell's longest prompt takes (the batcher's
``_prefill_suffix_fn``; where the traffic prefills whole prompts, its
one ``_prefill_fn`` program instead). On the chip it compiles for the attached device; ``--describe`` compiles for a described v5e from
the CPU (nothing runs; weights and pools are still allocated on the
host). PERF.md section 5 quotes these counts; ``tests/
test_chip_lowering.py`` guards a two-block version of them in tier 1.
"""

from __future__ import annotations

import argparse
import os
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("cell", help="a workload name of BENCHMARK.json")
    ap.add_argument(
        "--describe", action="store_true",
        help="compile for a described v5e:2x2 chip from the CPU",
    )
    ap.add_argument(
        "--out",
        help="also write the compiled texts here (decode; OUT.passN for"
        " the chunk-prefill passes)",
    )
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from adapt_tpu.runtime.continuous import ContinuousBatcher
    from chipbench import lm_engine
    from chipbench import manifest as mf
    from chipbench import traffic as tg

    manifest = mf.load(ROOT)
    cell = mf.cell(manifest, args.cell)
    config = mf.config_of(manifest, cell, ROOT)
    traffic = mf.traffic_of(manifest, cell, ROOT)
    model = dict(config["model"])
    serving = {**config["serving"], **traffic.get("serving", {})}
    sharding = None
    if args.describe:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
        sharding = SingleDeviceSharding(topo.devices[0])
    elif jax.devices()[0].platform != "tpu":
        raise SystemExit("no TPU here: pass --describe to compile for one")
    else:
        from adapt_tpu.utils.compile_cache import ensure_compile_cache

        ensure_compile_cache()
    lm, variables, shape = mf.part_of(config, "builder")(
        model, config["dtype"], 1
    )
    pairs = tg.templates(
        traffic, min(shape["max_len"], serving["prompt_buckets"][-1])
    )
    serving["pool_pages"] = lm_engine.pool_pages(
        serving, pairs, shape["max_len"]
    )
    srv = ContinuousBatcher(
        lm, variables,
        slots=serving["slots"], chunk=serving["chunk"],
        kv_layout=serving["kv_layout"], page_size=serving["page_size"],
        pool_pages=serving["pool_pages"],
        prefill_chunk=serving["prefill_chunk"],
        prompt_buckets=tuple(serving["prompt_buckets"]),
    )
    if args.describe:
        # The dispatchers ask the backend whether to compile or to
        # interpret a kernel; the program below is for the chip.
        jax.clear_caches()
        jax.default_backend = lambda: "tpu"

    def abstract(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    a_vars, a_caches, a_dstate, a_states = jax.tree.map(
        abstract, (srv._served, srv._caches, srv._dstate, srv._states)
    )
    a_table = abstract(
        jax.ShapeDtypeStruct(
            (len(srv.slots), srv._pager.pages_per_slot), jnp.int32
        )
    )
    if len(srv._groups) > 1:  # a page table a cache group
        a_table = (a_table,) * len(srv._groups)
    planes = jax.tree.leaves(srv._caches)
    # The embedding tables as the engine holds them (a row padded to
    # whole lane tiles where the model's is not: PERF.md section 6, PR 47).
    tables = {
        "/".join(str(k.key) for k in path[1:]): leaf.shape
        for path, leaf in jax.tree_util.tree_leaves_with_path(
            a_vars["embed"]
        )
    }

    def copies_of(text, shape):
        """As tests/conftest.pool_copies, by kind: a ``copy``, or a
        ``copy-start`` between two layouts, relays a buffer of ``shape``
        out; a ``copy-start`` between equal layouts stages it through
        fast memory (``S(1)``) as it is."""
        dims = re.escape(",".join(map(str, shape)))
        buf = r"\w+\[" + dims + r"\](\{[^}]*\})"
        sync = re.compile(r"= " + buf + r" copy\(")
        start = re.compile(r"= \(" + buf + ", " + buf + r".*\) copy-start\(")

        def tiles(layout):
            return re.sub(r"S\(\d+\)", "", layout)

        kinds: dict[str, int] = {}
        for line in text.splitlines():
            if m := sync.search(line):
                key = f"relayout: copy -> {m.group(1)}"
            elif m := start.search(line):
                same = tiles(m.group(1)) == tiles(m.group(2))
                key = (
                    f"{'move' if same else 'relayout'}: copy-start "
                    f"{m.group(2)} -> {m.group(1)}"
                )
            else:
                continue
            kinds[key] = kinds.get(key, 0) + 1
        return kinds

    where = (
        "described v5e, no chip" if args.describe
        else jax.devices()[0].device_kind
    )

    def report(tag, what, compiled):
        text = compiled.as_text()
        kinds = copies_of(text, planes[0].shape)
        total = sum(kinds.values())
        print(
            f"{tag} {args.cell}: pool {planes[0].shape} x {len(planes)} "
            f"planes, {what}: pool-shaped copy/copy-start ops {total} "
            f"({total / len(planes):.2f} a plane); Mosaic calls "
            f"{text.count('tpu_custom_call')}; temporaries "
            f"{compiled.memory_analysis().temp_size_in_bytes} B ({where})",
            flush=True,
        )
        for key, n in sorted(kinds.items()):
            print(f"    {n:4d} x {key}", flush=True)
        for name, shape in tables.items():
            kinds = copies_of(text, shape)
            relayouts = sum(
                n for key, n in kinds.items() if key.startswith("relayout")
            )
            print(
                f"    table {name} {shape}: relayouts {relayouts}, "
                f"moves {sum(kinds.values()) - relayouts}", flush=True,
            )
        return text

    text = report(
        "STEP_COPIES", f"slots {len(srv.slots)}, chunk {srv.chunk}",
        type(srv)._step_chunk.lower(
            srv, a_vars, a_caches, a_dstate, a_table, a_states,
            truncate=False, nucleus=False, epoch=srv._mesh_epoch,
        ).compile(),
    )
    if args.out:
        Path(args.out).write_text(text)

    def staged(shape, dtype):
        return abstract(jax.ShapeDtypeStruct(shape, dtype))

    # The chunked-prefill passes of the longest prompt the mix sends
    # (all but the last: the pass that samples adds the LM head, not a
    # pool operation), at the window widths the batcher pads them to.
    longest = max(p for p, _ in pairs)
    chunk, page = serving["prefill_chunk"], serving["page_size"]
    passes = -(-longest // chunk) if longest > chunk else 0
    if not passes:  # whole prompts: the one prefill program of the mix
        bucket = next(b for b in srv.prompt_buckets if b >= longest)
        text = report(
            "PREFILL_COPIES", f"whole prompt, bucket {bucket}",
            srv._prefill_fn(bucket).lower(
                a_vars, staged((1, bucket), jnp.int32),
                staged((2,), jnp.int32), staged((2,), jnp.float32),
                staged((1, 2), jnp.uint32), truncate=False, nucleus=False,
            ).compile(),
        )
        if args.out:
            Path(f"{args.out}.prefill").write_text(text)
    for i in range(passes - 1):
        n_pad = 1
        while n_pad < (i + 1) * chunk // page:
            n_pad *= 2
        text = report(
            "CHUNK_PREFILL_COPIES",
            f"pass {i + 1} of {passes}, window {n_pad} pages",
            srv._prefill_suffix_fn(chunk, n_pad, sample=False).lower(
                a_vars, a_caches, staged((n_pad,), jnp.int32),
                staged((1, chunk), jnp.int32),
                # (a model with recurrent state names the slot too)
                staged((4 if a_states else 3,), jnp.int32),
                staged((2,), jnp.float32), staged((1, 2), jnp.uint32),
                a_states, truncate=False, nucleus=False,
            ).compile(),
        )
        if args.out:
            Path(f"{args.out}.pass{i + 1}").write_text(text)
    srv.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
