"""Chain forwarding vs hub routing: the DCN-hop A/B.

Hub routing moves every stage boundary twice (worker→hub→worker: 2·S
transfers per request, SURVEY §3.2's critique of the reference Gen-2
topology); chain mode forwards activations worker→worker directly
(reference Gen-1, ``/root/reference/src/node.py:163-179``) so the hub
link carries only the final logits — S+1 data-plane transfers and no
hub NIC on the activation path.

Measured hermetically over localhost TCP (the reference's own test
affordance): 3 real worker processes serve ViT-tiny split in 3 stages;
the same request stream runs once hub-routed and once chained.
``vs_baseline`` = chain req/s ÷ hub req/s (>1 = direct hops win), and the
hub's measured result-frame bytes are reported for both modes — the
chained run's hub traffic must be exactly the final outputs.

CPU-backend by design: the topology cost being measured is
per-hop/transport, not device compute, and a chip belongs to one process
at a time — three worker processes on one host cannot share it, so the
children are pinned to ``JAX_PLATFORMS=cpu`` explicitly. Artifact:
``results/<round>/chain_forwarding.json`` (append-only JSONL).

Usage: ``python benchmarks/chain_forwarding.py [--requests 64] [--batch 8]``
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.common import int_flag, out_path  # noqa: E402  (no JAX)

OUT = out_path("chain_forwarding.json")
PORTS = (17741, 17742, 17743)


def metric_name(n_stages: int) -> str:
    return f"chain_forward_{n_stages}stage_req_per_sec"


def _spawn_worker(port: int):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))
    )
    return subprocess.Popen(
        [
            sys.executable,
            "-m",
            "adapt_tpu.comm.remote",
            "--port",
            str(port),
            "--heartbeat",
            "0.2",
        ],
        env=env,
        stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL,
    )


def _child(n_requests: int, batch: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from adapt_tpu.comm.remote import RemoteWorkerProxy
    from adapt_tpu.config import FaultConfig, ServeConfig
    from adapt_tpu.control.dispatcher import Dispatcher
    from adapt_tpu.graph import partition
    from adapt_tpu.models.vit import vit_block_cuts, vit_tiny

    g = vit_tiny()
    x = jnp.ones((batch, 32, 32, 3), jnp.float32)
    variables = g.init(jax.random.PRNGKey(0), x)
    cuts = vit_block_cuts(4, 3)
    plan = partition(g, cuts)
    y_ref = np.asarray(g.apply(variables, x))

    cfg = ServeConfig(
        fault=FaultConfig(
            lease_ttl_s=5.0,
            heartbeat_s=0.2,
            task_deadline_s=60.0,
            watchdog_period_s=0.5,
            startup_wait_s=20.0,
            configure_timeout_s=120.0,
        )
    )
    disp = Dispatcher(plan, variables, config=cfg)
    procs = [_spawn_worker(p) for p in PORTS]
    try:
        proxies = []
        for i, p in enumerate(PORTS):
            pr = RemoteWorkerProxy(
                f"chain-{i}",
                ("127.0.0.1", p),
                disp.registry,
                disp.result_queue,
                model_config={
                    "model": "vit_tiny",
                    "num_classes": 10,
                    "cuts": cuts,
                    "input_shape": [batch, 32, 32, 3],
                },
                fault=cfg.fault,
            )
            disp.attach_worker(pr)
            proxies.append(pr)
        disp.start()
        for pr in proxies:
            pr.start()
        # Pin each stage to its worker and pay every compile before either
        # timed phase (both modes then run the same warm executables).
        for i, pr in enumerate(proxies):
            pr.configure(i, None, plan.extract_variables(variables)[i])
        disp.serve_stream([x] * 3, timeout_per_request=120.0)

        def run(tag: str) -> tuple[float, int]:
            before = sum(p.result_bytes_received for p in proxies)
            t0 = time.perf_counter()
            outs = disp.serve_stream([x] * n_requests, 120.0)
            dt = time.perf_counter() - t0
            for y in outs:
                np.testing.assert_allclose(
                    np.asarray(y), y_ref, rtol=1e-5, atol=1e-5
                )
            return dt, sum(p.result_bytes_received for p in proxies) - before

        hub_s, hub_bytes = run("hub")
        disp.setup_chain([pr.worker_id for pr in proxies])
        disp.serve_stream([x] * 3, timeout_per_request=120.0)  # warm chain
        chain_s, chain_bytes = run("chain")
        assert disp._chain is not None, "chain fell back mid-measurement"

        hub_rps = n_requests / hub_s
        chain_rps = n_requests / chain_s
        print(
            json.dumps(
                {
                    "metric": metric_name(plan.num_stages),
                    "value": round(chain_rps, 2),
                    "unit": "req/sec",
                    "vs_baseline": round(chain_rps / hub_rps, 4),
                    "baseline": f"hub routing, same pool ({hub_rps:.1f} req/s)",
                    "platform": jax.devices()[0].platform,
                    "requests": n_requests,
                    "batch": batch,
                    "stages": plan.num_stages,
                    "hub_s": round(hub_s, 3),
                    "chain_s": round(chain_s, 3),
                    # Hub-link result-frame bytes: hub mode hauls every
                    # stage boundary; chain mode only the final logits.
                    "hub_result_bytes": hub_bytes,
                    "chain_result_bytes": chain_bytes,
                }
            ),
            flush=True,
        )
    finally:
        disp.shutdown()
        for p in procs:
            p.terminate()
        for p in procs:
            p.wait(timeout=10)


def main() -> int:
    n_requests = int_flag(sys.argv, "--requests", 64)
    batch = int_flag(sys.argv, "--batch", 8)
    if "--child" in sys.argv:
        _child(n_requests, batch)
        return 0
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    metric = metric_name(3)
    cmd = [
        sys.executable,
        os.path.abspath(__file__),
        "--child",
        "--requests",
        str(n_requests),
        "--batch",
        str(batch),
    ]
    try:
        proc = subprocess.run(
            cmd,
            capture_output=True,
            text=True,
            timeout=1800,
            env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        record = None
        for ln in proc.stdout.splitlines():
            if ln.strip().startswith("{"):
                try:
                    record = json.loads(ln)
                    break
                except json.JSONDecodeError:
                    continue
        if proc.returncode != 0 or record is None:
            record = {
                "metric": metric,
                "value": 0.0,
                "unit": "req/sec",
                "vs_baseline": 0.0,
                "error": (proc.stderr or proc.stdout or "")[-300:],
            }
    except subprocess.TimeoutExpired:
        record = {
            "metric": metric,
            "value": 0.0,
            "unit": "req/sec",
            "vs_baseline": 0.0,
            "error": "child timed out",
        }
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    mode = "a" if os.path.exists(OUT) else "w"
    with open(OUT, mode) as f:
        json.dump(record, f)
        f.write("\n")
    print(json.dumps(record), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
