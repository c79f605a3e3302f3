"""Compile-level overlap receipts: AOT-compile real train steps for a TPU
topology and assert async collectives are scheduled to HIDE behind
compute (r6, ISSUE 5).

The loss-is-finite dryrun proves sharded steps are CORRECT; it says
nothing about whether the zero-3 all-gathers, tp psums, or ep
all-to-alls actually overlap compute — the entire premise of those
layouts' throughput. The receipt lives in the compiler's SCHEDULED HLO:
XLA:TPU splits a hidden collective into an async pair
(``all-gather-start`` … ``all-gather-done``) and the latency-hiding
scheduler moves compute between the two. A collective that canNOT hide
schedules its ``-done`` immediately after its ``-start``.

This tool cross-compiles the fsdp / tp / flagship-MoE step on a virtual
TPU topology (``jax.experimental.topologies`` — no TPU chips needed,
only the compiler; libtpu ships in the image) through the REAL Trainer
(`state_template()` is ShapeDtypeStructs + shardings, so nothing is
materialized), then parses the scheduled module.

OVERLAP CRITERION (the one the CI stage enforces, documented here and in
docs/design.md): for every probed config,
  1. the scheduled module contains at least one async collective pair —
     a config whose collectives all compiled away would prove nothing;
  2. at least one pair of each PRESENT kind (all-gather, all-reduce,
     collective-permute, all-to-all) has >= 1 compute op (fusion / dot /
     convolution / while / custom-call) scheduled strictly between start
     and done — i.e. the scheduler found something to hide it behind;
  3. the fraction of overlapped pairs is reported per kind (the receipt
     artifact), but only total starvation (a kind where ZERO pairs
     overlap) fails the stage: small tails (e.g. the last all-gather of
     a layer stack with nothing left to overlap) are expected and
     visible in the artifact rather than gamed into the pass bar.

Usage:
    python -m tools.hloprobe [--probe fsdp,tp,flagship]
        [--topology v5e:2x4] [--json artifacts/hloprobe.json]

Exit 1 when any probed config violates the criterion — and when the TPU
compiler/topology cannot initialize at all (no libtpu in the
environment): a receipt that was not produced is a failure, not a skip.

The process is CPU-pinned, so the kernels' own dispatch
(``jax.default_backend()``) would hand the compiler the Pallas
interpreter or the jnp reference — a program the chip never runs. The
probe steers that answer to "tpu" around the lowering (here in the tool,
not through an option of the program), so what is compiled and cached is
the Mosaic-kernel program.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# AOT uses the CPU client as the host platform; libtpu is loaded only as
# a compiler. The metadata probes would otherwise stall ~60 s each
# looking for a GCE TPU VM that doesn't exist.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-8")
os.environ.setdefault("TPU_WORKER_ID", "0")
os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")

COMPUTE_RE = re.compile(
    r"%[\w.-]+ = \S+ (fusion|dot|convolution|while|custom-call)\("
)
COLLECTIVE_KINDS = ("all-gather", "all-reduce", "collective-permute",
                    "all-to-all", "reduce-scatter")
# plain async form: %all-gather-start.2 = ... all-gather-start(...)
# (the result type may be a TUPLE with spaces — match lazily to the op)
PLAIN_START_RE = re.compile(
    r"%(?P<name>[\w.-]+) = .*? (?P<kind>" +
    "|".join(COLLECTIVE_KINDS) + r")-start\("
)
PLAIN_DONE_RE = re.compile(
    r"(?:" + "|".join(COLLECTIVE_KINDS) +
    r")-done\([^%]*%(?P<start>[\w.-]+)"
)
# TPU async-collective-fusion form: the backend wraps the collective in a
#   %async-collective-start[.N] = (...) fusion(...), calls=%async_collective_fusion.M
#   %get-tuple-element.K = ... get-tuple-element((...) %async-collective-start[.N]), index=...
#   %async-collective-done[.N'] = ... fusion(... %get-tuple-element.K ...)
# pair; the collective's kind lives in the called fusion computation.
ACF_START_RE = re.compile(
    r"%(?P<name>[\w.-]+) = .*? fusion\(.*calls=%(?P<called>[\w.-]+)"
)
ACF_DONE_RE = re.compile(r"%(?P<name>async-collective-done[\w.-]*) = ")
GTE_RE = re.compile(
    r"%(?P<name>get-tuple-element[\w.-]*) = .*get-tuple-element\("
    r"[^%]*%(?P<producer>[\w.-]+)\)"
)
COMP_DEF_RE = re.compile(r"^%(?P<name>[\w.-]+) \(")


def _called_fusion_kinds(hlo_text: str) -> dict:
    """Map computation name -> collective kind for every called
    computation whose body holds a collective op (the TPU backend's
    async-collective-start wrappers call such computations — sometimes
    named async_collective_fusion.*, sometimes plain fused_computation.*
    with the collective inside)."""
    kinds = {}
    for block in hlo_text.split("\n\n"):
        header = block.lstrip().splitlines()[0] if block.strip() else ""
        m = COMP_DEF_RE.match(header)
        if not m:
            continue
        for kind in COLLECTIVE_KINDS:
            if re.search(rf"= \S+ {kind}[.(]", block):
                kinds[m.group("name")] = kind
                break
    return kinds


def analyze_schedule(hlo_text: str) -> dict:
    """Per async-pair overlap census over a scheduled HLO module.

    Scheduled modules list instructions in execution order within each
    computation, so "compute between start and done" is literally the
    compute lines between them (same computation body). Handles both
    async spellings: plain ``<kind>-start``/``-done`` ops and the TPU
    backend's ``async-collective-start``/``-done`` fusion wrappers
    (kind resolved through the called computation; pairing resolved
    through the done's get-tuple-element operands)."""
    called_kinds = _called_fusion_kinds(hlo_text)
    pairs = []  # (kind, n_compute_between)
    for body in hlo_text.split("\n\n"):
        lines = body.splitlines()
        open_starts = {}  # name -> (kind, compute_count_at_start)
        gte_producer = {}
        compute_seen = 0
        for ln in lines:
            m = GTE_RE.search(ln)
            if m:
                gte_producer[m.group("name")] = m.group("producer")
            m = PLAIN_DONE_RE.search(ln)
            if m and m.group("start") in open_starts:
                kind, at_start = open_starts.pop(m.group("start"))
                pairs.append((kind, compute_seen - at_start))
                continue
            m = ACF_DONE_RE.search(ln)
            if m:
                # the done wrapper is ALSO a fusion with calls= — match
                # it before the start patterns or it would be swallowed
                # as a new start
                for op in re.findall(r"%(get-tuple-element[\w.-]*)", ln):
                    start = gte_producer.get(op)
                    if start in open_starts:
                        kind, at_start = open_starts.pop(start)
                        pairs.append((kind, compute_seen - at_start))
                        break
                continue
            m = PLAIN_START_RE.search(ln)
            if m:
                open_starts[m.group("name")] = (m.group("kind"), compute_seen)
                continue
            m = ACF_START_RE.search(ln)
            if m and m.group("called") in called_kinds:
                # a fusion wrapping a collective: the async-start form
                # (named %async-collective-start.N at top level, plain
                # %fusion.N inside while bodies — the matching done
                # resolves it through its get-tuple-element operands)
                open_starts[m.group("name")] = (
                    called_kinds[m.group("called")], compute_seen)
                continue
            if COMPUTE_RE.search(ln) and "async-collective-" not in ln:
                compute_seen += 1
    kinds: dict = {}
    for kind, n in pairs:
        k = kinds.setdefault(kind, {"pairs": 0, "overlapped": 0})
        k["pairs"] += 1
        k["overlapped"] += 1 if n >= 1 else 0
    return {"kinds": kinds, "total_pairs": len(pairs)}


def _probe_configs():
    import jax.numpy as jnp

    # (name, preset kwargs, mesh axes, global batch, seq). Shapes are
    # the smallest where XLA's cost model bothers to ASYNCIFY: at toy
    # dims (d=64) the compiler leaves collectives synchronous — the
    # probe would report "nothing to check" rather than overlap.
    dense = dict(
        name="llama2-7b", d_model=512, n_layers=4, n_heads=8, n_kv_heads=8,
        d_ff=1408, vocab=8192, max_seq=512, dtype=jnp.bfloat16, remat=True,
    )
    return {
        # zero-3: params shard over fsdp, all-gathered per layer — the
        # all-gathers must hide behind the layer matmuls
        "fsdp": (dict(dense), {"fsdp": 8}, 16, 512),
        # megatron tp: row-parallel psums must hide behind the partial
        # matmuls; dp grads all-reduce behind the optimizer
        "tp": (dict(dense), {"dp": 4, "tp": 2}, 16, 512),
        # the flagship-MoE layout (mixtral ep x fsdp x dp, gmm dispatch):
        # ep all-to-alls + zero-3 all-gathers in one step
        "flagship": (dict(name="tiny-moe", d_model=256, n_heads=4,
                          n_kv_heads=4, d_ff=512, vocab=4096, max_seq=256,
                          dtype=jnp.bfloat16, remat=False, moe_top_k=2,
                          moe_dispatch="gmm"),
                     {"dp": 2, "fsdp": 2, "ep": 2}, 16, 256),
    }


def compile_step(topo_name: str, preset_kwargs: dict, mesh_axes: dict,
                 batch: int, seq: int) -> str:
    import numpy as np

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import Mesh

    from tf_operator_tpu.models.transformer import (
        init_transformer,
        lm_loss,
        preset,
        transformer_logical_axes,
    )
    from tf_operator_tpu.train.trainer import Trainer, TrainerConfig

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name=topo_name)
    devs = np.array(topo.devices).reshape(
        tuple(mesh_axes.values())
    )
    mesh = Mesh(devs, tuple(mesh_axes))
    kwargs = dict(preset_kwargs)
    cfg = preset(kwargs.pop("name"), **kwargs)
    trainer = Trainer(
        mesh,
        loss_fn=lambda p, b, e: lm_loss(p, b, cfg, mesh=mesh),
        init_fn=lambda k: init_transformer(k, cfg),
        logical_axes=transformer_logical_axes(cfg),
        config=TrainerConfig(optimizer="adamw", learning_rate=1e-3),
    )
    tmpl = trainer.state_template()
    batch_spec = jax.ShapeDtypeStruct((batch, seq), jnp.int32,
                                      sharding=trainer.batch_sharding)
    fn = trainer._build_step()
    from unittest import mock

    # the described devices are TPUs; make the kernels' dispatch see one
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        compiled = fn.lower(
            tmpl.params, tmpl.opt_state, tmpl.step, tmpl.extra, batch_spec
        ).compile()
    return compiled.as_text()


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--probe", default="fsdp,tp,flagship")
    p.add_argument("--topology", default="v5e:2x4",
                   help="virtual TPU topology (8 devices)")
    p.add_argument("--json", default=None, help="write the receipt artifact")
    p.add_argument("--dump-hlo-dir", default=None,
                   help="also save each config's scheduled HLO text")
    args = p.parse_args(argv)
    sys.path.insert(0, _REPO_ROOT)

    try:
        from jax.experimental import topologies

        topologies.get_topology_desc(platform="tpu",
                                     topology_name=args.topology)
    except Exception as exc:  # noqa: BLE001
        print(f"hloprobe FAILED: TPU compiler topology unavailable "
              f"({type(exc).__name__}: {exc}) — the receipt needs libtpu",
              file=sys.stderr)
        return 1

    configs = _probe_configs()
    results, failed = {}, []
    for name in args.probe.split(","):
        name = name.strip()
        if name not in configs:
            print(f"unknown probe config {name!r}; have {sorted(configs)}",
                  file=sys.stderr)
            return 2
        preset_kwargs, mesh_axes, batch, seq = configs[name]
        print(f"[{name}] AOT-compiling for {args.topology} "
              f"mesh={mesh_axes} ...", flush=True)
        txt = compile_step(args.topology, preset_kwargs, mesh_axes, batch,
                           seq)
        if args.dump_hlo_dir:
            os.makedirs(args.dump_hlo_dir, exist_ok=True)
            with open(os.path.join(args.dump_hlo_dir, f"{name}.hlo.txt"),
                      "w") as f:
                f.write(txt)
        res = analyze_schedule(txt)
        results[name] = res
        ok = res["total_pairs"] >= 1 and all(
            k["overlapped"] >= 1 for k in res["kinds"].values()
        )
        if not ok:
            failed.append(name)
        print(f"[{name}] {'PASS' if ok else 'FAIL'}: "
              f"{res['total_pairs']} async pairs; " + "; ".join(
                  f"{kind}: {v['overlapped']}/{v['pairs']} overlapped"
                  for kind, v in sorted(res["kinds"].items())
              ), flush=True)

    if args.json:
        with open(args.json, "w") as f:
            json.dump({"topology": args.topology, "results": results,
                       "failed": failed}, f, indent=2)
    if failed:
        print(f"hloprobe: overlap criterion FAILED for {failed}",
              file=sys.stderr)
        return 1
    print("hloprobe: overlap criterion met for all probed configs")
    return 0


if __name__ == "__main__":
    sys.exit(main())
