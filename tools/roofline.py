"""Measure the attached chip's *practical* matmul and convolution ceilings.

MFU is conventionally quoted against the datasheet peak, but the
achievable ceiling for real layer shapes is lower (layout, tiling, and
scheduling overheads inside XLA). This probe times chained bf16 ops
at configurable shapes entirely on-device (a `fori_loop` inside one jit,
so host dispatch is not part of a ~1.5 ms op's time) and
prints the effective TFLOP/s, i.e. the number a model at those shapes
should be compared against instead of the datasheet.

Usage:
  python -m tools.roofline [--m 16384] [--k 768] [--n 3072] [--iters 100]
  python -m tools.roofline --mode conv [--batch 128] [--image 224] [--fwd-only]

``--mode conv`` enumerates every convolution in the bench ResNet-50
(s2d stem, b=128, 224²) and measures each unique shape's sustained
TFLOP/s — forward alone and forward+backward (dgrad+wgrad via autodiff,
dy produced by a sum-of-squares head so the cotangent is a real tensor,
as in training). The FLOP-weighted aggregate over the layer inventory is
the *measured conv ceiling*: the MFU a ResNet-50 train step could reach
if convolutions were the only cost. bench.py reports achieved MFU
against both the 0.50 north star and this ceiling.

v5e (TPU v5 lite) matmul measurements for the record: [16384,768]x[768,3072]
pairs sustain ~103 TFLOP/s (52% of the 197 nominal bf16 peak);
[16384,4096]x[4096,4096] ~118 TFLOP/s (60%). A model step at 6ND-MFU 37%
on d=768 shapes is therefore at ~94% of what the chip actually gives
dense matmuls at that size once full-remat's recompute (+~33% FLOPs) is
accounted for.
"""

from __future__ import annotations

import argparse
import os
import sys
import time
from typing import List, NamedTuple

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def slope_per_iter(time_once, iters: int, retries: int = 2) -> float:
    """Seconds per iteration as the SLOPE between an ``iters``- and a
    5x-``iters``-sized run (r4 protocol, shared by every probe in this
    file): ``time_once(n)`` must build/warm an n-iteration loop and
    return the wall seconds of ONE synced execution. A single timed run
    divided by n carries every fixed cost of a call (dispatch, sync,
    fetch); the slope cancels them, whatever they are on the machine at
    hand. Host jitter can make an unlucky pair non-positive — retried,
    then raised, never silently reported as throughput."""
    for _ in range(retries + 1):
        lo, hi = time_once(iters), time_once(5 * iters)
        if hi > lo:
            return (hi - lo) / (4 * iters)
    raise RuntimeError(
        "non-positive timing slope: host jitter exceeded the signal; "
        "re-run with a larger --iters"
    )


def measure(m: int, k: int, n: int, iters: int) -> float:
    """Return effective TFLOP/s for a chained [m,k]x[k,n] -> [m,n]x[n,k] pair.

    r4 PROTOCOL FIX: the per-iteration time is the SLOPE between an
    ``iters``-iteration loop and a 5x one, both synced by a scalar fetch.
    A single-run protocol divides one wall time by iters and so carries
    the call's fixed costs into the rate; on an earlier installation
    that read a sub-ms body ~2x low. The slope does not depend on what
    those fixed costs are."""
    import jax
    import jax.numpy as jnp

    a = jax.random.normal(jax.random.PRNGKey(0), (m, k)).astype(jnp.bfloat16)
    w1 = jax.random.normal(jax.random.PRNGKey(1), (k, n)).astype(jnp.bfloat16) * 0.01
    w2 = jax.random.normal(jax.random.PRNGKey(2), (n, k)).astype(jnp.bfloat16) * 0.01

    def time_once(steps):
        @jax.jit
        def chain(a):
            # the w2 hop keeps shapes closed under iteration so the loop
            # stays on-device; *0.01 weights keep values finite
            a = jax.lax.fori_loop(0, steps, lambda i, a: (a @ w1) @ w2, a)
            return jnp.sum(a.astype(jnp.float32) ** 2)
        _ = float(chain(a))  # compile + warm; float() is the sync
        t0 = time.perf_counter()
        _ = float(chain(a))
        return time.perf_counter() - t0

    dt = slope_per_iter(time_once, iters)
    return 2 * m * k * n * 2 / dt / 1e12


class ConvShape(NamedTuple):
    """One convolution site in the network (count = occurrences)."""

    label: str
    h: int
    w: int
    cin: int
    cout: int
    kh: int
    kw: int
    stride: int
    count: int

    def out_hw(self):
        # SAME padding: ceil(h / stride)
        return (-(-self.h // self.stride), -(-self.w // self.stride))

    def fwd_flops(self, batch: int) -> float:
        oh, ow = self.out_hw()
        return 2.0 * batch * oh * ow * self.kh * self.kw * self.cin * self.cout


def resnet50_conv_inventory(image: int = 224) -> List[ConvShape]:
    """Every conv in the bench ResNet-50 (models/resnet.py, s2d stem),
    deduped with counts — derived from the SAME ResNetConfig the bench
    runs, so a config change (widths, depths) cannot leave this inventory
    silently stale against the published ceiling."""
    sys.path.insert(0, _REPO_ROOT)
    from tf_operator_tpu.models.resnet import ResNetConfig

    cfg = ResNetConfig.resnet50()
    shapes: List[ConvShape] = []
    h = image // 2  # after space-to-depth
    # s2d stem: 4x4/s1 conv on [h/2, w/2, 12] -> 64 channels
    shapes.append(ConvShape("stem-s2d", h, h, 12, 64, 4, 4, 1, 1))
    h //= 2  # maxpool /2 -> 56
    cin = 64
    for si, (n_blocks, width) in enumerate(
        zip(cfg.stage_sizes, cfg.widths)
    ):
        cout = width * 4
        for bi in range(n_blocks):
            stride = 2 if (si > 0 and bi == 0) else 1
            oh = h // stride
            shapes.append(ConvShape(f"s{si}b{bi}-1x1a", h, h, cin, width, 1, 1, 1, 1))
            shapes.append(ConvShape(f"s{si}b{bi}-3x3", h, h, width, width, 3, 3, stride, 1))
            shapes.append(ConvShape(f"s{si}b{bi}-1x1b", oh, oh, width, cout, 1, 1, 1, 1))
            if stride != 1 or cin != cout:
                shapes.append(ConvShape(f"s{si}b{bi}-proj", h, h, cin, cout, 1, 1, stride, 1))
            cin = cout
            h = oh
    # merge identical (h,w,cin,cout,k,stride) rows into counts
    merged = {}
    for s in shapes:
        key = (s.h, s.w, s.cin, s.cout, s.kh, s.kw, s.stride)
        if key in merged:
            m = merged[key]
            merged[key] = m._replace(count=m.count + 1)
        else:
            merged[key] = s
    return list(merged.values())


def measure_conv(
    batch: int, s: ConvShape, bwd: bool, target_flops: float = 2e12
) -> float:
    """Sustained TFLOP/s for one conv shape, scan-chained on-device.

    Methodology (matters a lot — naive probes read 3-5x low): the chain is
    a ``lax.scan`` over K DISTINCT stacked weights with the output feeding
    the next input — exactly how the model itself executes convs (stacked
    layer params under scan), so XLA schedules weight DMA/compute overlap
    the same way. A fori_loop re-invoking ONE conv on a loop-carried
    scalar measured 16 TFLOP/s where this chain measures 44+ on the same
    shape — that serialization artifact, not the hardware, was the old
    number. Shapes that don't close (cin != cout, stride > 1) are closed
    with a real 1x1 conv back to cin (mirroring the bottleneck's own
    1x1 pattern) plus a cheap spatial repeat for strides; the closer's
    FLOPs are counted in the denominator, so the row is the efficiency of
    the (conv + closer) unit — labeled ``+1x1`` in the table.

    ``bwd`` differentiates the WHOLE chain (0.5*sum(y²) head, so dy is a
    real tensor): per-layer dgrad+wgrad through scan, 3x fwd FLOPs — the
    training-step execution shape.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    fan_in = s.kh * s.kw * s.cin
    oh, ow = s.out_hw()
    needs_closer = (s.cin != s.cout) or (s.stride != 1)
    flops_iter = s.fwd_flops(batch)
    if needs_closer:
        flops_iter += 2.0 * batch * oh * ow * s.cout * s.cin  # 1x1 closer
    total_mult = 3.0 if bwd else 1.0
    iters = max(4, min(64, int(target_flops / (flops_iter * total_mult))))

    x0 = (
        jax.random.normal(jax.random.PRNGKey(0), (batch, s.h, s.w, s.cin))
        .astype(jnp.bfloat16)
    )
    ks = (
        jax.random.normal(
            jax.random.PRNGKey(1), (iters, s.kh, s.kw, s.cin, s.cout)
        )
        * (2.0 / fan_in) ** 0.5
    ).astype(jnp.bfloat16)
    kc = (
        jax.random.normal(jax.random.PRNGKey(2), (iters, 1, 1, s.cout, s.cin))
        * (2.0 / s.cout) ** 0.5
    ).astype(jnp.bfloat16)

    def conv(x_, k_, stride=1):
        return lax.conv_general_dilated(
            x_,
            k_,
            window_strides=(stride, stride),
            padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )

    def chain(x, stacked):
        def body(x, kpair):
            k, k_close = kpair
            y = conv(x, k, s.stride)
            if needs_closer:
                y = conv(y, k_close)
                if s.stride != 1:
                    y = jnp.repeat(jnp.repeat(y, s.stride, axis=1), s.stride, axis=2)
                    y = y[:, : s.h, : s.w]
            # keep magnitudes bounded across the chain
            return (y * jnp.bfloat16(0.5)).astype(jnp.bfloat16), None

        out, _ = lax.scan(body, x, stacked)
        return out

    if bwd:
        def head(x, stacked):
            return 0.5 * jnp.sum(jnp.square(chain(x, stacked).astype(jnp.float32)))

        run = jax.jit(jax.grad(head, argnums=(0, 1)))

        def fetch(r):
            return float(r[0][0, 0, 0, 0])
    else:
        run = jax.jit(chain)

        def fetch(r):
            return float(r[0, 0, 0, 0])

    stacked = (ks, kc)
    fetch(run(x0, stacked))  # compile + sync

    # slope between 2 and 10 back-to-back dispatch bursts: a single
    # burst's time carries the call's fixed costs (see slope_per_iter).
    def time_once(reps):
        t0 = time.perf_counter()
        r = None
        for _ in range(reps):  # back-to-back dispatch, one final fetch
            r = run(x0, stacked)
        fetch(r)
        return time.perf_counter() - t0

    dt = slope_per_iter(time_once, 2)
    return flops_iter * total_mult * iters / dt / 1e12


def _measure_with_retry(batch, s, bwd, attempts: int = 3) -> float:
    """A transient runtime error must not kill a 30-minute sweep; the
    last attempt's error is raised."""
    for i in range(attempts):
        try:
            return measure_conv(batch, s, bwd=bwd)
        except Exception as exc:  # jax.errors.JaxRuntimeError et al.
            if i == attempts - 1:
                raise
            print(f"  (retry {s.label} {'bwd' if bwd else 'fwd'}: {exc})", flush=True)
            time.sleep(5.0)


def convnet_ceiling(batch: int, image: int, bwd: bool, reps: int = 4) -> float:
    """THE conv ceiling: the bench ResNet-50 with batch-norm deleted —
    exact conv/relu/residual/pool/head graph at exact shapes, so XLA
    schedules cross-op overlap exactly as in the real model. Per-layer
    chains (the table above this in the output) systematically undershoot
    — an isolated conv chain denies XLA the inter-op pipelining the full
    network enjoys — so the achievable-MFU comparison uses THIS number:
    train MFU / convnet_ceiling(bwd) = fraction of the conv-stack's
    achievable rate the full step (BN + loss + optimizer on top) reaches.
    Returns TFLOP/s using the SAME flops_per_image accounting bench.py
    uses, so the ratio to bench MFU is apples-to-apples."""
    import jax
    import jax.numpy as jnp

    sys.path.insert(0, _REPO_ROOT)
    from tf_operator_tpu.models.resnet import (
        ResNetConfig,
        _conv,
        _stem_s2d,
        init_resnet,
    )

    cfg = ResNetConfig.resnet50()
    params, _ = init_resnet(jax.random.PRNGKey(0), cfg)

    def fwd(params, x):
        x = x.astype(jnp.bfloat16)
        x = _stem_s2d(x, params["stem"]["conv"])
        x = jax.nn.relu(x)
        x = jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME"
        )
        for si, n_blocks in enumerate(cfg.stage_sizes):
            for bi in range(n_blocks):
                bp = params[f"stage{si}"][bi]
                stride = 2 if (si > 0 and bi == 0) else 1
                y = jax.nn.relu(_conv(x, bp["conv1"]))
                y = jax.nn.relu(_conv(y, bp["conv2"], stride))
                y = _conv(y, bp["conv3"])
                shortcut = _conv(x, bp["proj"], stride) if "proj" in bp else x
                x = jax.nn.relu(y + shortcut)
        x = jnp.mean(x.astype(jnp.float32), axis=(1, 2))
        return x @ params["head"]["w"] + params["head"]["b"]

    x0 = jax.random.normal(jax.random.PRNGKey(1), (batch, image, image, 3))
    # Device loop (K iterations inside ONE program, chained by a tiny
    # input perturbation from the previous output): one program long
    # enough that host dispatch jitter is a small share of its time.
    K = 8

    def keepalive(tree):
        # Reduce EVERY leaf into the carry: a carry touching only one
        # element lets XLA dead-code-eliminate the rest of the computation
        # (measured: a head-bias-only carry "ran" the backward at 130% of
        # peak — i.e. mostly deleted). Means are cheap vs the convs.
        return sum(
            jnp.mean(leaf.astype(jnp.float32))
            for leaf in jax.tree_util.tree_leaves(tree)
        ) * 1e-30

    if bwd:
        g = jax.grad(lambda p, x: 0.5 * jnp.sum(jnp.square(fwd(p, x))))

        def body(i, carry):
            s, x = carry
            return (keepalive(g(params, x + s)), x)
    else:
        def body(i, carry):
            s, x = carry
            return (keepalive(fwd(params, x + s)), x)

    run = jax.jit(
        lambda x: jax.lax.fori_loop(0, K, body, (jnp.float32(0.0), x))[0]
    )
    float(run(x0))  # compile + sync
    t0 = time.perf_counter()
    r = None
    for _ in range(reps):
        r = run(x0)
    float(r)
    dt = (time.perf_counter() - t0) / (reps * K)
    flops = cfg.flops_per_image(image) * batch * (3.0 if bwd else 1.0)
    return flops / dt / 1e12


def conv_roofline(batch: int, image: int, fwd_only: bool = False) -> int:
    """Measure every ResNet-50 conv shape; print per-layer rows and the
    FLOP-weighted ceiling (the MFU a train step could reach if convs were
    the only cost)."""
    sys.path.insert(0, _REPO_ROOT)
    from tf_operator_tpu.train.compile_cache import enable as enable_compile_cache

    enable_compile_cache()  # ~36 probe kernels; persist compiles across runs

    import jax

    from tf_operator_tpu.train.metrics import peak_flops_per_chip

    dev = jax.devices()[0]
    peak = peak_flops_per_chip(dev) / 1e12
    inv = resnet50_conv_inventory(image)
    modes = ("fwd",) if fwd_only else ("fwd", "fwd+bwd")
    print(
        f"# conv roofline: ResNet-50 b={batch} {image}² bf16 NHWC on "
        f"{getattr(dev, 'device_kind', dev.platform)} (peak {peak:.0f} TFLOP/s)",
        flush=True,
    )
    print(f"# {'layer':<12} {'shape':<30} {'count':>5} " + " ".join(f"{m:>9}" for m in modes))
    totals = {m: [0.0, 0.0] for m in modes}  # [weighted flops, weighted time]
    for s in inv:
        row = []
        for m in modes:
            tf = _measure_with_retry(batch, s, bwd=(m == "fwd+bwd"))
            row.append(tf)
            wf = s.fwd_flops(batch) * s.count * (3.0 if m == "fwd+bwd" else 1.0)
            totals[m][0] += wf
            totals[m][1] += wf / (tf * 1e12)
        closer = "+1x1" if (s.cin != s.cout or s.stride != 1) else ""
        desc = f"{s.h}x{s.w}x{s.cin}->{s.cout} k{s.kh} s{s.stride}{closer}"
        print(
            f"  {s.label:<12} {desc:<30} {s.count:>5} "
            + " ".join(f"{tf:>5.1f}T/{tf / peak:>4.0%}" for tf in row),
            flush=True,
        )
    for m in modes:
        wf, wt = totals[m]
        ceiling = wf / wt / 1e12
        print(
            f"# weighted per-layer {m}: {ceiling:.1f} TFLOP/s = "
            f"{ceiling / peak:.1%} of peak (diagnostic — isolated chains "
            "undershoot, see convnet ceiling below)",
            flush=True,
        )
    # The honest ceiling: the full conv-only network (exact graph, XLA's
    # real cross-op scheduling). Train MFU should be judged against the
    # fwd+bwd number.
    cf = convnet_ceiling(batch, image, bwd=False)
    print(
        f"# convnet (BN-free ResNet-50) fwd ceiling: {cf:.1f} TFLOP/s = "
        f"{cf / peak:.1%} of peak",
        flush=True,
    )
    if not fwd_only:
        cb = convnet_ceiling(batch, image, bwd=True)
        print(
            f"# convnet (BN-free ResNet-50) fwd+bwd ceiling: {cb:.1f} TFLOP/s "
            f"= {cb / peak:.1%} of peak -> max train MFU if convs were the "
            f"whole step: {cb / peak:.1%}",
            flush=True,
        )
    return 0


def measure_attn(b, t, h, d, causal, impl, iters=20, h_kv=None,
                 repeat_from=None):
    """Sustained ms/step for one attention config, fwd+bwd (training path),
    chained on-device like the other probes (tiny data-dependent weight
    perturbation defeats loop hoisting). ``h_kv`` < h measures the
    GQA-native path (k/v carry h_kv heads end to end); ``repeat_from``
    instead measures the pre-r3 layout — k/v allocated at repeat_from
    heads and jnp.repeat-expanded to h INSIDE the differentiated function,
    so the broadcast copy and its backward group-sum are part of the
    measurement."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    sys.path.insert(0, _REPO_ROOT)
    from tf_operator_tpu.ops.flash_attention import (
        flash_attention,
        reference_attention,
    )

    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    kv_heads = repeat_from or h_kv or h
    q = jax.random.normal(keys[0], (b, t, h, d)).astype(jnp.bfloat16)
    k = jax.random.normal(keys[1], (b, t, kv_heads, d)).astype(jnp.bfloat16)
    v = jax.random.normal(keys[2], (b, t, kv_heads, d)).astype(jnp.bfloat16)

    if impl == "flash":
        def base_attn(q_, k_, v_):
            return flash_attention(q_, k_, v_, causal=causal, force_kernel=True)
    else:
        def base_attn(q_, k_, v_):
            return reference_attention(q_, k_, v_, causal=causal)
    if repeat_from:
        g_rep = h // repeat_from

        def attn(q_, k_, v_):
            return base_attn(
                q_, jnp.repeat(k_, g_rep, axis=2), jnp.repeat(v_, g_rep, axis=2)
            )
    else:
        attn = base_attn

    def head(q_, k_, v_):
        return 0.5 * jnp.sum(jnp.square(attn(q_, k_, v_).astype(jnp.float32)))

    g = jax.grad(head, argnums=(0, 1, 2))

    def body(i, carry):
        gq, gk, gv = g(q + carry.astype(jnp.bfloat16), k, v)
        return (gq[0, 0, 0, 0] + gk[0, 0, 0, 0] + gv[0, 0, 0, 0]).astype(
            jnp.float32
        ) * 1e-30

    # slope protocol (see slope_per_iter) — the old single-call timing
    # overstated ms-scale bodies 2-4x and COMPRESSED A/B ratios toward 1
    # (the r2 flash-vs-dense table understates the kernel's true
    # advantage; its gate decisions were conservative, not wrong).
    def time_once(n):
        run = jax.jit(lambda c: lax.fori_loop(0, n, body, c))
        float(run(jnp.float32(0.0)))  # compile + sync
        t0 = time.perf_counter()
        float(run(jnp.float32(0.0)))
        return time.perf_counter() - t0

    return slope_per_iter(time_once, iters) * 1e3  # ms per fwd+bwd


def gqa_roofline(d: int = 128) -> int:
    """GQA A/B (r3, VERDICT #2 done-bar): flash fwd+bwd at a GQA shape —
    native h_kv-head K/V vs the pre-r3 materialized repeat (k/v expanded
    to h heads before the kernel). Reports the time ratio and the K/V
    activation bytes each layout keeps resident per layer."""
    sys.path.insert(0, _REPO_ROOT)
    from tf_operator_tpu.train.compile_cache import enable as enable_compile_cache

    enable_compile_cache()
    import jax

    dev = jax.devices()[0]
    h, h_kv = 16, 2
    print(f"# GQA flash fwd+bwd, causal, bf16, hd={d}, {h}q/{h_kv}kv heads on "
          f"{getattr(dev, 'device_kind', dev.platform)}")
    print(f"# {'b':>3} {'t':>6}  {'repeat ms':>10} {'native ms':>10} "
          f"{'speedup':>8} {'kv MiB rep':>10} {'kv MiB nat':>10}")
    for b, t in ((4, 2048), (2, 4096), (1, 8192)):
        # pre-r3 layout: h_kv-head K/V repeat-expanded INSIDE the step
        rep = measure_attn(b, t, h, d, True, "flash", repeat_from=h_kv)
        nat = measure_attn(b, t, h, d, True, "flash", h_kv=h_kv)
        mib = lambda heads: 2 * b * t * heads * d * 2 / 2**20
        print(f"  {b:>3} {t:>6}  {rep:>10.2f} {nat:>10.2f} "
              f"{rep / nat:>7.2f}x {mib(h):>10.1f} {mib(h_kv):>10.1f}")
    return 0


def attn_roofline(d: int = 64) -> int:
    """flash-vs-dense crossover table at head_dim ``d`` (fwd+bwd, causal),
    the measurement behind flash_attention's dispatch gate."""
    sys.path.insert(0, _REPO_ROOT)
    from tf_operator_tpu.train.compile_cache import enable as enable_compile_cache

    enable_compile_cache()
    import jax

    dev = jax.devices()[0]
    print(f"# attention fwd+bwd, causal, bf16, hd={d} on "
          f"{getattr(dev, 'device_kind', dev.platform)} (b x t x h chosen ~const tokens)")
    print(f"# {'b':>3} {'t':>6} {'h':>3}  {'dense ms':>9} {'flash ms':>9} {'flash/dense':>11}")
    for b, t, h in ((8, 512, 12), (4, 1024, 12), (2, 2048, 12), (1, 4096, 12), (1, 8192, 12)):
        dense = measure_attn(b, t, h, d, True, "dense")
        flash = measure_attn(b, t, h, d, True, "flash")
        print(f"  {b:>3} {t:>6} {h:>3}  {dense:>9.2f} {flash:>9.2f} {dense / flash:>10.2f}x")
    return 0


def moe_roofline(tokens: int = 32768, d: int = 768, f: int = 3072,
                 n_experts: int = 8, k_top: int = 1,
                 capacity_factor: float = 2.0, iters: int = 40) -> int:
    """Decompose the single-chip MoE step cost at bench shapes (r4,
    VERDICT item 2: where do the other 82% of active-MFU go?).

    Times fwd+bwd of five bodies over the same [T, d] activations:
      dense        one SwiGLU over all T tokens at [T,d]x[d,f] — the
                   "active FLOPs at ideal shape" reference
      experts-loop the expert compute exactly as _moe_single runs it
                   (fori_loop over E, [C,d]x[d,f] each) on a fixed inbox
      experts-vmap the same compute as ONE batched [E,C,d]x[E,d,f]
                   einsum chain (what removing the loop buys)
      routing      moe_apply with an identity expert_fn — router + sort/
                   scatter/gather + combine, zero expert FLOPs
      full         the real moe layer (router + dispatch + experts +
                   combine)
    and prints a table: ms, implied active-MFU (6·T_active·params_mlp /
    time), and the share of `full`. Padding waste is structural:
    capacity rows C·E = cf·k·T, so the expert stage runs cf·k× the
    active FLOPs — measured directly by the experts rows.
    """
    import jax
    import jax.numpy as jnp

    from tf_operator_tpu.parallel.moe import moe_apply

    from tf_operator_tpu.train.metrics import peak_flops_per_chip

    dev = jax.devices()[0]
    peak = peak_flops_per_chip(dev)
    cap = max(1, int(capacity_factor * k_top * tokens / n_experts))
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 6)
    x = (jax.random.normal(ks[0], (tokens, d)) * 0.02).astype(jnp.bfloat16)
    router = (jax.random.normal(ks[1], (d, n_experts)) * 0.02).astype(jnp.bfloat16)
    wp = {
        "w_gate": (jax.random.normal(ks[2], (n_experts, d, f)) * 0.02).astype(jnp.bfloat16),
        "w_up": (jax.random.normal(ks[3], (n_experts, d, f)) * 0.02).astype(jnp.bfloat16),
        "w_down": (jax.random.normal(ks[4], (n_experts, f, d)) * 0.02).astype(jnp.bfloat16),
    }
    dense_w = {k_: v[0] for k_, v in wp.items()}
    inbox = (jax.random.normal(ks[5], (n_experts, cap, d)) * 0.02).astype(jnp.bfloat16)

    def swiglu(w, t):
        return (jax.nn.silu(t @ w["w_gate"]) * (t @ w["w_up"])) @ w["w_down"]

    def expert_fn(w, t):
        return swiglu(w, t)

    # Every body differentiates wrt activations AND weights — the
    # training cost shape (fwd 2 + bwd 4 FLOPs per param-token); an
    # input-only grad would skip the dW matmuls and over-report MFU 1.5x.
    def body_dense(args):
        return jnp.sum(swiglu(args["w"], args["x"]).astype(jnp.float32) ** 2)

    def body_experts_loop(args):
        inbox, w = args["x"], args["w"]

        def run(e, acc):
            w_e = jax.tree_util.tree_map(lambda a: a[e], w)
            return acc + jnp.sum(swiglu(w_e, inbox[e]).astype(jnp.float32) ** 2)
        return jax.lax.fori_loop(0, n_experts, run, jnp.float32(0.0))

    def body_experts_vmap(args):
        out = jax.vmap(swiglu, in_axes=(0, 0))(args["w"], args["x"])
        return jnp.sum(out.astype(jnp.float32) ** 2)

    def body_routing(args):
        gl = args["x"] @ args["w"]
        out = moe_apply(args["x"], gl, {"w": jnp.zeros((n_experts, 1))},
                        lambda w, t: t, None, capacity_factor=capacity_factor,
                        k_top=k_top, dropped="zero")
        return jnp.sum(out.astype(jnp.float32) ** 2)

    def body_full(args):
        gl = args["x"] @ args["wr"]
        out = moe_apply(args["x"], gl, args["w"], expert_fn, None,
                        capacity_factor=capacity_factor, k_top=k_top,
                        dropped="zero")
        return jnp.sum(out.astype(jnp.float32) ** 2)

    def body_full_gmm(args):
        # r5: the padding-free grouped-matmul layer (ops/grouped_matmul)
        gl = args["x"] @ args["wr"]
        out = moe_apply(args["x"], gl, args["w"], expert_fn, None,
                        k_top=k_top, dispatch_impl="gmm")
        return jnp.sum(out.astype(jnp.float32) ** 2)

    def body_experts_gmm(args):
        # the grouped matmul alone on ACTIVE rows (uniform groups): the
        # "experts-vmap at cf" rows vs this one isolates the padding
        # term. r6: the down projection runs with the fused combine
        # epilogue (row_scale) exactly as the shipped layer does, and
        # the dw walk behind this row is the regridded
        # (expert, col-tile, block-walk) kernel — this row is where its
        # retired per-step accumulator round trip shows up.
        from tf_operator_tpu.ops.grouped_matmul import gmm as gmm_op

        xs, w = args["x"], args["w"]
        nb_blocks = xs.shape[0] // 256  # the kernel's shipping block size
        # nondecreasing block→expert map covering every block even when
        # nb_blocks % n_experts != 0 (a repeat() of nb//E entries would
        # leave the tail blocks reading out of the prefetch buffer)
        be = (
            jnp.arange(nb_blocks, dtype=jnp.int32) * n_experts // nb_blocks
        ).astype(jnp.int32)
        zg = gmm_op(xs, w["w_gate"], be)
        zu = gmm_op(xs, w["w_up"], be)
        out = gmm_op(jax.nn.silu(zg) * zu, w["w_down"], be,
                     row_scale=args["rs"])
        return jnp.sum(out.astype(jnp.float32) ** 2)

    # Active-FLOP reference: 6·(3·d·f)·T_active fwd+bwd matmul FLOPs
    # (2 fwd + 4 bwd per param-token).
    active_flops = 6 * (3 * d * f) * tokens * k_top

    def timeit(fn, arg):
        # fori_loop INSIDE one jit (the file-header protocol): host
        # dispatch stays out of the body's time. Feeding each
        # iteration's grad back into its input keeps the body
        # loop-varying so XLA cannot hoist it. The sync is a scalar
        # fetch (no reason to move the carry), and the per-iteration
        # time is the SLOPE between a short and a long loop, cancelling
        # every fixed cost.
        g = jax.grad(fn)

        def time_once(n):
            @jax.jit
            def loop(args):
                def body(i, args):
                    ga = g(args)
                    return jax.tree_util.tree_map(
                        lambda a, da: (a + 1e-6 * da).astype(a.dtype),
                        args, ga)
                args = jax.lax.fori_loop(0, n, body, args)
                return jnp.sum(
                    jax.tree_util.tree_leaves(args)[0].astype(jnp.float32) ** 2
                )
            _ = float(loop(arg))  # compile + warm
            t0 = time.perf_counter()
            _ = float(loop(arg))
            return time.perf_counter() - t0

        return slope_per_iter(time_once, iters)

    x_active = (jax.random.normal(ks[5], (tokens * k_top, d)) * 0.02).astype(
        jnp.bfloat16
    )
    rows = [
        ("dense", body_dense, {"x": x, "w": dense_w}),
        ("experts-loop", body_experts_loop, {"x": inbox, "w": wp}),
        ("experts-vmap", body_experts_vmap, {"x": inbox, "w": wp}),
        ("experts-gmm", body_experts_gmm,
         {"x": x_active, "w": wp,
          "rs": jnp.ones((tokens * k_top,), jnp.float32)}),
        ("routing", body_routing, {"x": x, "w": router}),
        ("full", body_full, {"x": x, "wr": router, "w": wp}),
        ("full-gmm", body_full_gmm, {"x": x, "wr": router, "w": wp}),
    ]
    results = {}
    for name, fn, arg in rows:
        results[name] = timeit(fn, arg)
    full_ms = results["full"] * 1e3
    print(f"MoE roofline on {getattr(dev, 'device_kind', dev.platform)}: "
          f"T={tokens} d={d} f={f} E={n_experts} top-{k_top} cf={capacity_factor} "
          f"C={cap} (expert rows = {capacity_factor * k_top:.2f}x active)")
    print(f"  {'stage':<14} {'ms':>8}  {'active-MFU':>10}  {'% of full':>9}")
    for name, _, _ in rows:
        dt = results[name]
        amfu = active_flops / dt / peak
        print(f"  {name:<14} {dt * 1e3:>8.2f}  {amfu:>10.1%}  "
              f"{dt * 1e3 / full_ms:>9.1%}")
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--mode", choices=("matmul", "conv", "attn", "gqa", "moe"),
                   default="matmul")
    p.add_argument("--m", type=int, default=16384)
    p.add_argument("--k", type=int, default=768)
    p.add_argument("--n", type=int, default=3072)
    p.add_argument("--iters", type=int, default=100)
    p.add_argument("--batch", type=int, default=128)
    p.add_argument("--image", type=int, default=224)
    p.add_argument("--fwd-only", action="store_true")
    p.add_argument("--d", type=int, default=None,
                   help="head_dim (default: 64 for --mode attn, 128 for gqa)")
    p.add_argument("--k-top", type=int, default=1, help="--mode moe: top-k")
    p.add_argument("--cf", type=float, default=2.0,
                   help="--mode moe: capacity factor")
    args = p.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        # a chip's practical ceilings cannot be measured on something else
        print(f"roofline needs a TPU; jax found {dev.platform!r} "
              f"({getattr(dev, 'device_kind', '?')})", file=sys.stderr)
        return 1
    if args.mode == "conv":
        return conv_roofline(args.batch, args.image, args.fwd_only)
    if args.mode == "attn":
        return attn_roofline(args.d or 64)
    if args.mode == "gqa":
        return gqa_roofline(args.d or 128)
    if args.mode == "moe":
        return moe_roofline(tokens=args.m, k_top=args.k_top,
                            capacity_factor=args.cf)

    tflops = measure(args.m, args.k, args.n, args.iters)
    print(
        f"[{args.m},{args.k}]x[{args.k},{args.n}] chained bf16 matmul on "
        f"{getattr(dev, 'device_kind', dev.platform)}: {tflops:.1f} TFLOP/s"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
