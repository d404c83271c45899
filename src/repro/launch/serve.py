"""Serving launcher: quantize a checkpoint and serve batched requests.

The paper's deployment pipeline end-to-end: load (or init) fp weights ->
apply a quantization policy (DQ3_K_M by default) -> shard onto the mesh ->
serve batched generation requests.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --reduced \
      --policy DQ3_K_M --requests 4 --max-new 16

:func:`main` is :func:`parse_args` -> :func:`load_quantized` ->
:func:`serve_requests`; ``chip_smoke.py`` drives the same three.  The
persistent compilation cache goes where ``launch/compile_cache.py`` says.
"""

from __future__ import annotations

import argparse

import jax.numpy as jnp
import numpy as np

from ..checkpoint import checkpoint as ckpt
from ..configs import get_config
from ..core import quantize_params, get_policy, model_size
from ..models import spec as mspec
from ..models.model import Model
from ..serving.engine import Engine, Request
from ..serving.sampler import SamplerConfig
from .compile_cache import enable_compile_cache
from .mesh import describe_mesh, mesh_from_spec


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--policy", default="DQ3_K_M")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--slots", type=int, default=4,
                    help="continuous-batching decode slots")
    ap.add_argument("--sequential", action="store_true",
                    help="serve one request at a time (throughput baseline)")
    ap.add_argument("--page-size", type=int, default=0,
                    help="tokens per KV-cache page; >0 pages the pooled "
                         "cache so memory scales with live tokens instead "
                         "of slots x max_len")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="page-pool capacity (default: worst case for "
                         "--slots x --max-len)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="admission chunk length in tokens; long prompts "
                         "stream in chunk-by-chunk interleaved with decode "
                         "(default: whole prompt in one chunk)")
    ap.add_argument("--kernel", default=None,
                    choices=("fused", "gather"),
                    help="paged decode implementation: 'fused' attends KV "
                         "pages in place via the Pallas flash-decode "
                         "kernels (decode bandwidth scales with live "
                         "tokens), 'gather' re-materialises the dense "
                         "slots x max-len view each step (reference). "
                         "Default: REPRO_PAGED_KERNEL env, else fused. "
                         "Only meaningful with --page-size > 0")
    ap.add_argument("--kv-quant", default=None,
                    choices=("q8_0", "q4_0", "dq"),
                    help="quantize the paged KV cache pools: 'q8_0' int8 "
                         "values + per-row f32 scales (~4x less cache "
                         "memory and decode page traffic), 'q4_0' "
                         "nibble-packed int4 (~8x), 'dq' dynamic per-layer "
                         "bitwidth — sensitive layers (first/last, MLA "
                         "latents) stay q8_0, the rest drop to q4_0 (the "
                         "matching fused kernels are selected "
                         "automatically).  Requires --page-size > 0")
    ap.add_argument("--scheduler", default="reserve",
                    choices=Engine.SCHEDULERS,
                    help="'reserve' admits only when the pool can hold a "
                         "request's worst case (never preempts); 'preempt' "
                         "admits in (priority, arrival) order, lets the "
                         "pool oversubscribe, and swaps the lowest-class/"
                         "youngest lane's KV pages to host memory when it "
                         "runs dry.  Requires --page-size > 0")
    ap.add_argument("--priority-classes", type=int, default=1,
                    help="number of request classes; request i gets class "
                         "i %% N (0 = most urgent).  Only meaningful with "
                         "--scheduler preempt")
    ap.add_argument("--oversubscribe", type=float, default=0.0,
                    help="size the page pool to this fraction of the "
                         "worst case for --slots lanes (e.g. 0.5 = half), "
                         "forcing preemption pressure; overrides "
                         "--num-pages.  Only meaningful with "
                         "--scheduler preempt")
    ap.add_argument("--swap-budget-bytes", type=int, default=None,
                    help="cap on host bytes held by swapped-out lanes; "
                         "evictions past the cap restart the request "
                         "instead of swapping.  Only meaningful with "
                         "--scheduler preempt")
    ap.add_argument("--mesh", default="none",
                    help="serving mesh: 'none' (default, single device), "
                         "'host' (1 x all local devices) or 'DxM' (e.g. "
                         "2x4 = data=2, model=4).  The ENGINE lays both "
                         "the weights and the paged KV pools out on this "
                         "mesh — there is no separate weight-sharding "
                         "step, so the two can never disagree.  Requires "
                         "--page-size > 0; CPU repro: set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=8 before "
                         "launch")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request deadline from serve start; requests "
                         "that exceed it retire with status='timeout'")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="admission cap: requests past this bound are load-"
                         "shed immediately with status='shed' instead of "
                         "queueing")
    ap.add_argument("--chaos", type=int, default=None, metavar="SEED",
                    help="serve under a seeded random fault plan (swap "
                         "failures, allocator outages, latency spikes, "
                         "page corruption, NaN logits, cancels) and report "
                         "what was injected; same seed, same schedule.  "
                         "See docs/chaos.md")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--temperature", type=float, default=0.6)
    ap.add_argument("--top-p", type=float, default=0.95)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--dtype", default="bfloat16",
                    choices=("bfloat16", "float32"),
                    help="activation dtype; unquantized KV pools are "
                         "stored in it too")
    return ap.parse_args(argv)


def load_quantized(args):
    """``(cfg, quantized params)``: the checkpoint in ``--ckpt-dir`` (else
    weights initialised from ``--seed``) quantized with ``--policy``."""
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    policy = get_policy(args.policy)

    if args.ckpt_dir and ckpt.latest_step(args.ckpt_dir) is not None:
        tree, _ = ckpt.restore(args.ckpt_dir)
        params = {k[len("param/"):]: v for k, v in tree.items()
                  if k.startswith("param/")}
        print(f"loaded checkpoint from {args.ckpt_dir}")
    else:
        params = mspec.init_params(cfg, args.seed)

    rep = model_size(cfg, policy)
    print(f"quantizing {cfg.name} with {policy.name}: "
          f"{rep.gib:.2f} GiB @ {rep.avg_bits:.2f} bits/weight "
          f"(bf16 would be {rep.total_params * 2 / 1024**3:.2f} GiB)")
    return cfg, quantize_params(cfg, params, policy)


def serve_requests(args, cfg, qparams):
    """Build the engine ``args`` describe, serve ``--requests`` seeded
    random prompts, print each request and the stats report, and return
    ``(engine, finished requests)``."""
    mesh = mesh_from_spec(args.mesh)
    # no weight-sharding step here: the Engine lays the weights out on the
    # mesh it serves on (Engine(mesh=...) shards, Engine(mesh=None)
    # rejects pre-sharded params), so the "weights sharded on one mesh,
    # engine serving unsharded" split is structurally impossible
    model = Model(cfg, dtype=jnp.dtype(args.dtype))
    plan = None
    if args.chaos is not None:
        from ..serving.faults import FaultPlan
        plan = FaultPlan.random(args.chaos,
                                rids=list(range(args.requests)))
        print(f"chaos mode: seed {args.chaos}, "
              f"{len(plan.faults)} faults armed "
              f"({', '.join(f.kind for f in plan.faults)})")
    engine = Engine(model, qparams, max_len=args.max_len,
                    sampler=SamplerConfig(args.temperature, args.top_p),
                    page_size=args.page_size, num_pages=args.num_pages,
                    prefill_chunk=args.prefill_chunk, kernel=args.kernel,
                    kv_quant=args.kv_quant, scheduler=args.scheduler,
                    swap_budget_bytes=args.swap_budget_bytes, mesh=mesh,
                    faults=plan, max_queue=args.max_queue)
    if mesh is not None:
        print(f"serving on mesh {describe_mesh(mesh)} "
              f"({mesh.size} devices: weights + paged KV pools sharded)")

    slots = min(args.slots, args.requests)
    if args.oversubscribe and args.page_size:
        from ..models import paged
        n_full = (paged.pages_for(args.max_len, args.page_size)
                  if engine._has_full else 0)
        n_ring = (paged.pages_for(engine._ring_len, args.page_size)
                  if engine._has_ring else 0)
        worst = paged.RESERVED_PAGES + slots * (n_full + n_ring)
        # floor: one request's worst case must always fit
        engine.num_pages = max(paged.RESERVED_PAGES + n_full + n_ring,
                               int(args.oversubscribe * worst))
        print(f"oversubscribed pool: {engine.num_pages} pages "
              f"({args.oversubscribe:.2f}x of the {worst}-page worst case)")

    rng = np.random.default_rng(args.seed)
    reqs = [Request(rid=i,
                    prompt=list(rng.integers(4, cfg.vocab_size,
                                             rng.integers(4, 12))),
                    max_new=args.max_new,
                    priority=i % max(args.priority_classes, 1),
                    deadline_s=args.deadline_s)
            for i in range(args.requests)]
    if args.sequential:
        done = engine.serve_sequential(reqs, seed=args.seed)
    else:
        done = engine.serve(reqs, slots=slots,
                            seed=args.seed)
    for r in done:
        tag = "" if r.status in ("", "ok") else f"  [{r.status}]"
        print(f"req {r.rid}: prompt[{len(r.prompt)}] -> {r.out}{tag}")
    stats = engine.last_stats
    print(stats.report())
    if plan is not None:
        hits = ", ".join(f"{f['kind']}@{f['step']}" for f in stats.fault_log)
        print(f"chaos: {stats.faults_injected} faults landed"
              + (f" ({hits})" if hits else ""))
    return engine, done


def main(argv=None):
    enable_compile_cache()
    args = parse_args(argv)
    cfg, qparams = load_quantized(args)
    _, done = serve_requests(args, cfg, qparams)
    return done


if __name__ == "__main__":
    main()
