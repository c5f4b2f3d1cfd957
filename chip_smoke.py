"""Chip smoke: the train and serve paths, once, on the TPU, at full width.

    python chip_smoke.py                 # the chip run; exit 0 = every phase passed
    python chip_smoke.py --rehearse-cpu  # tiny shapes on the CPU backend: a
                                         # harness check, never a chip result

One process drives every visible chip through the entry points a user calls:

- ``kernels``        every Pallas kernel a TPU selector can reach, compiled by
                     Mosaic at the LM's width and matched to its jnp reference
- ``expert_layer``   ``HeldMoE`` at the Xing cell's shape (4,096 tokens, d 3,584,
                     8 of 64 experts held, 4 a token) on both of its paths:
                     the short buffers its routing fits, and the whole-size
                     ones a routing collapsed onto this shard (forced by a
                     bias) overflows into; forward and every gradient against
                     the plain float32 loop over the held experts
- ``train_resnet50`` ``Optimizer(...).optimize()`` on ResNet-50 (s2d stem), 224x224
- ``train_lm``       the same path on the 12-layer d768 ``Transformer(mode="lm")``
                     at seq 1024 (flash-attention forward + Pallas backward
                     pair inside the ZeRO-1 ``shard_map`` step)
- ``serve_lm``       ``InferenceModel`` -> ``warmup()`` -> ``ServingServer`` +
                     ``HttpFrontend``; concurrent ``/generate`` requests over
                     localhost with drawn prompt lengths; zero compiles after
                     warmup; the kernel path compared with the plain jnp path

Weights are random (seeded), depth and width are the models' own.  Every phase
prints ``platform``, ``device_kind``, the device count and PASS/FAIL; any FAIL
makes the exit code non-zero.  Without a TPU it exits 2 before building
anything and prints no result.  The last stdout line of a passing chip run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

Seconds printed here are set-up and wall seconds of a smoke, not performance.
"""

import argparse
import gc
import json
import os
import resource
import shutil
import sys
import tempfile
import threading
import time
import traceback

import numpy as np

# stated tolerances (max |kernel - reference| / max |reference|, reference in
# float32 at matmul precision "highest"):
TOL_F32 = 2e-3    # VPU/f32 kernels: only exp/rsqrt approximations differ
TOL_F32_SUM = 1e-5  # float32 multiply-adds and sums in another order, nothing
#                     approximated (the mixing's one-pass backward: 2e-7 on
#                     the v5e at (4, 4096, 3584), PR 35)
TOL_MXU = 2e-2    # kernels whose dots take bf16 MXU passes (KERNELS_r04 used
#                   0.02 forward / 0.05 backward for the same reason)
TOL_MXU_BWD = 5e-2
# flash attention under the bf16 policy (operands cast to bf16, float32
# accumulation and statistics), measured on the v5e at (2, 12, 1024, 64)
# (PR 29): forward 3.1e-3, dq 8.1e-3, dk 8.5e-3, dv 2.6e-3 (PR 22's float32-fed
# kernel: 1.7e-3 and <= 8.8e-3; XLA's own attention under the same policy reads
# 4.0e-3 forward and 1.8e-2 on dq at this shape); the tolerances are 6x that.
# serving, per generated token: both engines run the same bf16 projections
# and FFN (the TPU compute dtype) and the same prefill; in the decode steps the
# kernel does its attention dots in f32 on the VPU, the jnp path in single bf16
# MXU passes (relative 2^-8 per product).  Measured on the v5e (PR 22):
# 4.2e-4 and 5.3e-5 nats per token; the tolerance is ~20x that.  A wrong page
# walk or mask is a difference of order 1.
TOL_LOGP_NATS = 0.01

FULL = dict(
    resnet=dict(hw=224, classes=1000, batch_per_chip=128, steps=8),
    lm=dict(layers=12, d=768, heads=12, vocab=32768, seq=1024,
            batch_per_chip=8, steps=6),
    serve=dict(slots=8, page_size=16, pages_per_slot=64, prompt_chunk=32,
               max_new=12, requests=8, prompt_lens=(24, 640)),
    kern=dict(slots=8, heads=12, hd=64, page=16, nb=64, chunk=5,
              flash_b=2, flash_s=1024, ffn=(768, 3072), bs_block=(128, 128),
              ln_rows=4096, mm=(256, 768, 3072), hc=(4, 4096, 3584),
              sala=dict(heads=4, first=28, t=32768, hd=128, kernel=32,
                        stride=16, block=64, topk=64, init_blocks=1,
                        window=2048),
              ssd=dict(heads=64, hd=64, state=128, t=16384, chunk=256),
              conv=dict(t=16384, wide=8512, offset=4096, width=4352,
                        taps=4)),
    moe=dict(tokens=4096, d=3584, hidden=1024, experts=64, held=(8, 8), k=4),
)
TINY = dict(
    resnet=dict(hw=32, classes=10, batch_per_chip=8, steps=8),
    lm=dict(layers=2, d=32, heads=2, vocab=64, seq=32,
            batch_per_chip=4, steps=6),
    serve=dict(slots=4, page_size=4, pages_per_slot=8, prompt_chunk=4,
               max_new=4, requests=4, prompt_lens=(3, 24)),
    kern=dict(slots=2, heads=2, hd=16, page=4, nb=2, chunk=3,
              flash_b=1, flash_s=32, ffn=(32, 64), bs_block=(16, 16),
              ln_rows=16, mm=(32, 128, 128), hc=(4, 64, 128),
              sala=dict(heads=2, first=30, t=512, hd=32, kernel=32,
                        stride=16, block=64, topk=4, init_blocks=1,
                        window=128),
              ssd=dict(heads=2, hd=8, state=16, t=200, chunk=64),
              conv=dict(t=1152, wide=640, offset=128, width=384, taps=4)),
    moe=dict(tokens=256, d=32, hidden=16, experts=16, held=(4, 2), k=2),
)


class Smoke:
    def __init__(self, sizes, device):
        self.sz = sizes
        self.dev = device
        self.results = {}
        self.lm = None  # (model, variables) handed from train_lm to serve_lm

    def say(self, phase, msg):
        d = self.dev
        print(f"[chip_smoke] phase={phase} platform={d['platform']} "
              f"device_kind={d['kind']!r} devices={d['count']} {msg}",
              flush=True)

    def run(self, phase, fn):
        """One phase.  A boundary that must keep running: a failed phase is
        recorded with its traceback and the next one still reports."""
        t0 = time.perf_counter()
        detail = None
        try:
            fn()
        except Exception:  # noqa: BLE001 — reported, and fails the run
            detail = traceback.format_exc()
        ok = self.results[phase] = detail is None
        rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss // 1024
        self.say(phase, f"result={'PASS' if ok else 'FAIL'} "
                        f"seconds={time.perf_counter() - t0:.1f} "
                        f"host_peak_rss_mb={rss}")
        if not ok:
            print(detail, flush=True)
        gc.collect()

    def close(self, phase, what, got, want, tol) -> bool:
        """max |got - want| / max |want| within ``tol``, and said so."""
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        err = float(np.max(np.abs(got - want))
                    / max(float(np.max(np.abs(want))), 1e-6))
        fine = bool(np.isfinite(got).all()) and err <= tol
        self.say(phase, f"{what} rel_err={err:.2e} tol={tol} "
                        f"{'ok' if fine else 'MISMATCH'}")
        return fine

    # -- phase: kernels ------------------------------------------------------
    def kernels(self):
        import jax
        import jax.numpy as jnp

        from bigdl_tpu.nn.attention import dot_product_attention
        from bigdl_tpu.ops import autotune
        from bigdl_tpu.ops.block_sparse import (block_sparse_matmul,
                                                expand_mask)
        from bigdl_tpu.ops.common import default_interpret
        from bigdl_tpu.ops.flash_attention import (flash_attention,
                                                   paged_decode_attention,
                                                   paged_verify_attention,
                                                   resolve_blocks)
        from bigdl_tpu.ops.fused import fused_layernorm
        from bigdl_tpu.ops.quantized import (dequantize_pages, int8_matmul,
                                             quantize_pages)

        k = self.sz["kern"]
        S, h, d, page, nb, C = (k["slots"], k["heads"], k["hd"], k["page"],
                                k["nb"], k["chunk"])
        self.say("kernels", f"pallas_interpret={default_interpret()} "
                            f"(False = compiled by Mosaic)")
        if self.dev["platform"] == "tpu" and default_interpret():
            raise AssertionError("kernels would run in interpret mode on TPU")
        rs = np.random.RandomState(0)
        bad = []

        def check(name, got, want, tol):
            if not self.close("kernels", f"kernel={name}", got, want, tol):
                bad.append(name)

        def ref(fn, *a):
            with jax.default_matmul_precision("highest"):
                return jax.jit(fn)(*a)

        # flash attention, forward and backward, as MultiHeadAttention calls
        # it in the LM (f32 q/k/v, causal)
        B, T = k["flash_b"], k["flash_s"]
        q, kk, v = (jnp.asarray(rs.randn(B, h, T, d), jnp.float32)
                    for _ in range(3))
        causal = jnp.tril(jnp.ones((T, T), bool))

        def loss_of(att):
            return lambda q, kk, v: jnp.sum(att(q, kk, v) ** 2)

        flash = lambda q, kk, v: flash_attention(q, kk, v, causal=True)
        plain = lambda q, kk, v: dot_product_attention(q, kk, v, mask=causal)
        check("flash_attention_fwd", jax.jit(flash)(q, kk, v),
              ref(plain, q, kk, v), TOL_MXU)
        g_k = jax.jit(jax.grad(loss_of(flash), argnums=(0, 1, 2)))(q, kk, v)
        g_r = ref(jax.grad(loss_of(plain), argnums=(0, 1, 2)), q, kk, v)
        for n, a, b in zip("qkv", g_k, g_r):
            check(f"flash_attention_bwd_d{n}", a, b, TOL_MXU_BWD)

        # the hyper-connection's stream-wide products at the Xing cell's
        # shape: the one-pass backward (what HyperConnection takes on a TPU)
        # against autodiff of the plain expressions.  Only the chip shows
        # Mosaic's layout of the per-token columns and the accumulators
        # carried over the chunks of d
        from bigdl_tpu.nn.hyper_connection import read_out, write_back
        from bigdl_tpu.ops.hc_mix import mix_backward

        n, tok, wide = k["hc"]
        kx = jax.random.split(jax.random.PRNGKey(35), 7)
        xs, gs, through = (jax.random.normal(kk_, (n, tok, wide))
                           for kk_ in kx[:3])
        ys, du = (jax.random.normal(kk_, (tok, wide)) for kk_ in kx[3:5])
        cf = jax.random.uniform(kx[5], (n, n + 1, tok))
        hp = jax.random.uniform(kx[6], (n, tok))

        def pre_bwd(h, du, x, add):
            dx, _, dh = mix_backward(h[None], du[None], x, add=add)
            return dx, dh[0]

        got = jax.jit(mix_backward)(cf, gs, xs, ys)
        want = ref(lambda c, g, x, y: jax.vjp(
            write_back, x, y, c[:, :n], c[:, n])[1](g), cf, gs, xs, ys)
        for name, a, b in zip(("dx", "dy", "dres", "dpost"),
                              got[:2] + (got[2][:, :n], got[2][:, n]), want):
            check(f"hc_mix_post_bwd_{name}", a, b, TOL_F32_SUM)
        got = jax.jit(pre_bwd)(hp, du, xs, through)
        dx, dh = ref(lambda h, du, x: jax.vjp(read_out, x, h)[1](du),
                     hp, du, xs)
        check("hc_mix_pre_bwd_dx", got[0], dx + through, TOL_F32_SUM)
        check("hc_mix_pre_bwd_dh", got[1], dh, TOL_F32_SUM)
        del xs, gs, through, got, want, dx
        self.sala_kernels(check, ref)
        self.ssd_kernels(check, ref)
        self.conv_kernels(check, ref)

        # paged decode / verify attention over a page pool, f32 and int8
        P = S * nb
        kp = jnp.asarray(rs.randn(P, h, page, d), jnp.float32)
        vp = jnp.asarray(rs.randn(P, h, page, d), jnp.float32)
        pt = jnp.asarray(rs.permutation(P).reshape(S, nb), jnp.int32)
        lens = jnp.asarray(rs.randint(0, nb * page - C, (S,)), jnp.int32)
        qd = jnp.asarray(rs.randn(S, h, C, d), jnp.float32)
        kq, ks = quantize_pages(kp)
        vq, vs = quantize_pages(vp)

        def gathered(pool):  # (P,h,page,d)[pt] -> (S,h,nb*page,d)
            return pool[pt].transpose(0, 2, 1, 3, 4).reshape(
                S, h, nb * page, d)

        def attend(qc, kpool, vpool, first):
            # query c of slot s sits at position first[s]+c, attends <= it
            pos = first[:, None] + jnp.arange(qc.shape[2])[None, :]
            valid = jnp.arange(nb * page)[None, None, :] <= pos[:, :, None]
            sc = jnp.einsum("shcd,shkd->shck", qc, gathered(kpool)) \
                * d ** -0.5
            w = jax.nn.softmax(jnp.where(valid[:, None], sc, -1e30), -1)
            return jnp.einsum("shck,shkd->shcd", w, gathered(vpool))

        for tag, pools, scales in (
                ("f32", (kp, vp), {}),
                ("int8", (kq, vq), dict(k_scales=ks, v_scales=vs))):
            deq = pools if not scales else (dequantize_pages(kq, ks),
                                            dequantize_pages(vq, vs))
            check(f"paged_decode_attention_{tag}",
                  jax.jit(lambda q1: paged_decode_attention(
                      q1, *pools, pt, lens, **scales))(qd[:, :, 0]),
                  ref(attend, qd[:, :, :1], *deq, lens)[:, :, 0], TOL_F32)
            check(f"paged_verify_attention_{tag}",
                  jax.jit(lambda qc: paged_verify_attention(
                      qc, *pools, pt, lens, **scales))(qd),
                  ref(attend, qd, *deq, lens), TOL_F32)

        # block-sparse matmul (the speculative draft's FFN), the compute
        # dtype's inputs, forward and dx
        K, N = k["ffn"]
        bk, bn = k["bs_block"]
        from bigdl_tpu.tensor.policy import get_compute_dtype

        cdt = get_compute_dtype()
        x = jnp.asarray(rs.randn(S, K), cdt)
        w = jnp.asarray(rs.randn(K, N) / np.sqrt(K), cdt)
        mask = rs.rand(K // bk, N // bn) < 0.5
        mask[0, :] = True
        wm = jnp.where(jnp.asarray(expand_mask(mask, K, N, bk, bn)),
                       w.astype(jnp.float32), 0.0)
        bs = lambda x: block_sparse_matmul(x, w, mask, block_k=bk,
                                           block_n=bn)
        dense = lambda x: x.astype(jnp.float32) @ wm
        check("block_sparse_matmul_fwd", jax.jit(bs)(x), ref(dense, x),
              TOL_MXU)
        check("block_sparse_matmul_dx",
              jax.jit(jax.grad(lambda x: jnp.sum(
                  bs(x).astype(jnp.float32) ** 2)))(x),
              ref(jax.grad(lambda x: jnp.sum(dense(x) ** 2)), x),
              TOL_MXU_BWD)

        # fused layernorm, int8 matmul
        rows = k["ln_rows"]
        xl = jnp.asarray(rs.randn(rows, K), jnp.float32)
        gam = jnp.asarray(rs.randn(K), jnp.float32)
        bet = jnp.asarray(rs.randn(K), jnp.float32)

        def ln(x, g, b):
            mu = x.mean(-1, keepdims=True)
            var = ((x - mu) ** 2).mean(-1, keepdims=True)
            return (x - mu) * jax.lax.rsqrt(var + 1e-5) * g + b

        check("fused_layernorm", jax.jit(fused_layernorm)(xl, gam, bet),
              ref(ln, xl, gam, bet), TOL_F32)
        m_, k_, n_ = k["mm"]
        a8 = rs.randint(-127, 128, (m_, k_)).astype(np.int8)
        w8 = rs.randint(-127, 128, (k_, n_)).astype(np.int8)
        check("int8_matmul", jax.jit(int8_matmul)(a8, w8),
              a8.astype(np.int64) @ w8.astype(np.int64), 0.0)

        # the default tiles every auto-resolved call above used (flash
        # attention's: its block rule's pick for operands it casts to the
        # compute dtype)
        flash_fwd, flash_bwd = resolve_blocks(q.shape, T, cdt)
        tiles = {
            "flash_attention_fwd": flash_fwd,
            "flash_attention_bwd": flash_bwd,
            "flash_attention_decode": autotune.resolve(
                "flash_attention_decode", autotune.decode_attention_key(
                    S, h, page, d, nb, jnp.float32)),
            "block_sparse_matmul": autotune.resolve(
                "block_sparse_matmul", autotune.block_sparse_key(
                    S, K, N, bk, bn, cdt)),
            "fused_layernorm": autotune.resolve(
                "fused_layernorm", autotune.rows_key(rows, K, jnp.float32)),
            "int8_matmul": autotune.resolve(
                "int8_matmul", autotune.matmul_key(m_, k_, n_, jnp.int8)),
        }
        self.say("kernels", f"autotune_cache={autotune.get_cache().path} "
                            f"tiles={json.dumps(tiles)}")
        if bad:
            raise AssertionError(f"kernels off their reference: {bad}")

    def sala_kernels(self, check, ref):
        """Lightning attention and the block-sparse kernels at the
        MiniCPM-SALA cell's shape (4 held heads of 128, 32,768 positions,
        the slowest decays, MiniCPM4's selection), forward and dq/dk/dv
        against autodiff of their plain forms: Mosaic compiles the reversed
        chunk walk and the transposed-selection dk/dv only here (tier-1
        runs them interpreted).  The plain forms go a block of queries at
        a time under ``jax.checkpoint``: (heads, T, T) scores are 17 GB."""
        import jax
        import jax.numpy as jnp

        from bigdl_tpu.ops.lightning_attention import (alibi_slopes,
                                                       lightning_attention)
        from bigdl_tpu.ops.sparse_attention import (select_blocks,
                                                    sparse_attention)

        c = self.sz["kern"]["sala"]
        n, t, hd, blk = c["heads"], c["t"], c["hd"], c["block"]
        qb = min(1024, t)
        ks = jax.random.split(jax.random.PRNGKey(38), 4)
        q, kk, v, g = (jax.random.normal(x, (1, n, t, hd)) for x in ks)
        slopes = alibi_slopes(32, c["first"], n)
        pos = jnp.arange(t)

        def by_blocks(weights):
            """o (n, t, hd) of q, k, v (n, t, hd): ``weights(i, scores (n,
            qb, t))`` is block i's attention."""
            def f(q, k, v):
                @jax.checkpoint
                def one(i):
                    qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, 1)
                    w = weights(i, jnp.einsum("nqd,nsd->nqs", qi, k)
                                * hd ** -0.5)
                    return jnp.einsum("nqs,nsd->nqd", w, v)
                o = jax.lax.map(one, jnp.arange(t // qb))
                return o.transpose(1, 0, 2, 3).reshape(n, t, hd)
            return f

        def vjp_of(f):
            return lambda q, k, v, g: jax.vjp(f, q, k, v)[1](g)

        def decayed(i, s):
            lag = (i * qb + jnp.arange(qb))[:, None] - pos[None, :]
            decay = jnp.exp(-jnp.asarray(slopes)[:, None, None]
                            * jnp.maximum(lag, 0))
            return jnp.where(lag >= 0, s * decay, 0.0)

        light = lambda q, k, v: lightning_attention(q, k, v, slopes)
        plain = lambda q, k, v: by_blocks(decayed)(q[0], k[0], v[0])[None]
        check("lightning_attention_fwd", jax.jit(light)(q, kk, v),
              ref(plain, q, kk, v), TOL_MXU)
        for name, a, b in zip("qkv", jax.jit(vjp_of(light))(q, kk, v, g),
                              ref(vjp_of(plain), q, kk, v, g)):
            check(f"lightning_attention_bwd_d{name}", a, b, TOL_MXU_BWD)

        # one group of ``n`` query heads on key/value head 0
        sel = jax.jit(lambda q, k: select_blocks(
            q[None], k[:, :1], kernel=c["kernel"], stride=c["stride"],
            block=blk, topk=c["topk"], init_blocks=c["init_blocks"],
            window=c["window"]))(q, kk)
        nblk = t // blk

        def chosen(i, s):
            rows = jax.lax.dynamic_slice_in_dim(sel[0, 0], i * qb, qb, 0)
            taken = jnp.zeros((qb, nblk + 1), bool).at[
                jnp.arange(qb)[:, None], jnp.where(rows >= 0, rows, nblk)
            ].set(True)[:, :nblk]
            mask = jnp.repeat(taken, blk, 1) & (
                (i * qb + jnp.arange(qb))[:, None] >= pos[None, :])
            return jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)

        sparse = lambda q, k, v: sparse_attention(
            q[:, None], k[:, :1], v[:, :1], sel, block=blk)[:, 0]
        group = lambda x: jnp.broadcast_to(x[0, :1], (n, t, hd))
        dense = lambda q, k, v: by_blocks(chosen)(
            q[0], group(k), group(v))[None]
        check("sparse_attention_fwd", jax.jit(sparse)(q, kk, v),
              ref(dense, q, kk, v), TOL_MXU)
        for name, a, b in zip("qkv", jax.jit(vjp_of(sparse))(q, kk, v, g),
                              ref(vjp_of(dense), q, kk, v, g)):
            check(f"sparse_attention_bwd_d{name}", a, b, TOL_MXU_BWD)

    def ssd_kernels(self, check, ref):
        """The SSD kernels (Mamba-2's selective scan) at the
        granite-4.0-h-micro cell's mixer (64 heads of 64, a state of 128,
        chunks of 256 over 16,384 positions), forward and the six gradients
        against autodiff of the token-by-token recurrence: Mosaic compiles
        the heads-innermost grid, the reversed chunk walk and the (64, 128)
        float32 states in VMEM only here.  The recurrence goes in segments
        of 128 positions under ``jax.checkpoint``: its per-position states
        would be 32 GB."""
        import jax
        import jax.numpy as jnp

        from bigdl_tpu.ops.ssd import ssd

        c = self.sz["kern"]["ssd"]
        h, p, n, t = c["heads"], c["hd"], c["state"], c["t"]
        ks = jax.random.split(jax.random.PRNGKey(40), 6)
        x = jax.random.normal(ks[0], (1, t, h, p))
        dt = jax.nn.softplus(jax.random.normal(ks[1], (1, t, h)) - 3.0)
        a_log = jnp.log(jax.random.uniform(ks[2], (h,), minval=1.0,
                                           maxval=16.0))
        b, cc = (jax.random.normal(k_, (1, t, n)) for k_ in ks[3:5])
        d = jnp.ones((h,))
        g = jax.random.normal(ks[5], (1, t, h, p))
        seg = 128 if t % 128 == 0 else t

        def plain(x, dt, a_log, b, cc, d):
            a = -jnp.exp(a_log)

            def step(s, inputs):
                xt, dtt, bt, ct = inputs
                s = (jnp.exp(dtt * a)[:, None, None] * s
                     + (dtt[:, None] * xt)[:, :, None] * bt[None, None, :])
                return s, jnp.einsum("hpn,n->hp", s, ct)

            @jax.checkpoint
            def segment(s, inputs):
                return jax.lax.scan(step, s, inputs)

            split = lambda v: v[0].reshape((t // seg, seg) + v.shape[2:])
            _, y = jax.lax.scan(segment, jnp.zeros((h, p, n)),
                                tuple(map(split, (x, dt, b, cc))))
            return (y.reshape(t, h, p) + d[:, None] * x[0])[None]

        mine = lambda x, dt, a_log, b, cc, d: ssd(
            x, dt, -jnp.exp(a_log), b, cc, d, chunk=c["chunk"])
        args = (x, dt, a_log, b, cc, d)
        check("ssd_fwd", jax.jit(mine)(*args), ref(plain, *args), TOL_MXU)

        def vjp_of(f):
            return lambda g, *a: jax.vjp(f, *a)[1](g)

        for name, got, want in zip(
                ("x", "dt", "a_log", "b", "c", "d"),
                jax.jit(vjp_of(mine))(g, *args),
                ref(vjp_of(plain), g, *args)):
            check(f"ssd_bwd_d{name}", got, want, TOL_MXU_BWD)

    def conv_kernels(self, check, ref):
        """Mamba-2's causal convolution with its bias and SiLU
        (``ops/causal_conv.py``) at the granite-4.0-h-micro cell's mixer
        (x‖B‖C, 4,352 channels, read at 4,096 of ``W_in``'s 8,512-wide
        output, 4 taps, 16,384 positions), forward and the three gradients
        against autodiff of ``causal_taps``: float32 both ways, so only the
        ``exp`` of the SiLU and the order of the sums differ.  Mosaic
        compiles the halo blocks and the unaligned lane slices only
        here."""
        import jax
        import jax.numpy as jnp

        from bigdl_tpu.nn.short_conv import causal_taps
        from bigdl_tpu.ops.causal_conv import causal_conv

        c = self.sz["kern"]["conv"]
        t, off, width = c["t"], c["offset"], c["width"]
        ks = jax.random.split(jax.random.PRNGKey(41), 4)
        u = jax.random.normal(ks[0], (1, t, c["wide"]))
        bound = c["taps"] ** -0.5
        w = jax.random.uniform(ks[1], (c["taps"], width), minval=-bound,
                               maxval=bound)
        b = jax.random.uniform(ks[2], (width,), minval=-bound, maxval=bound)
        g = jax.random.normal(ks[3], (1, t, width))

        def plain(u, w, b):
            return jax.nn.silu(causal_taps(u[..., off:off + width], w) + b)

        mine = lambda u, w, b: causal_conv(u, w, b, offset=off)
        check("causal_conv_fwd", jax.jit(mine)(u, w, b), ref(plain, u, w, b),
              TOL_F32)

        def vjp_of(f):
            return lambda g, *a: jax.vjp(f, *a)[1](g)

        for name, got, want in zip(("u", "w", "b"),
                                   jax.jit(vjp_of(mine))(g, u, w, b),
                                   ref(vjp_of(plain), g, u, w, b)):
            check(f"causal_conv_bwd_d{name}", got, want, TOL_F32)

    # -- phase: expert layer -------------------------------------------------
    def expert_layer(self):
        """The grouped product leaves the rows past its groups unwritten on
        the TPU, forward and backward (PERF.md, PR 28), and which rows those
        are differs between the short buffers and the whole-size ones: both
        are held to the plain loop here, where only the chip can show it."""
        import jax
        import jax.numpy as jnp

        from bigdl_tpu.parallel.moe import (HeldMoE, held_capacity,
                                            route_sigmoid_topk)

        z = self.sz["moe"]
        t, d, k, (first, count) = z["tokens"], z["d"], z["k"], z["held"]
        moe = HeldMoE(z["experts"], z["hidden"], k, held=z["held"], scale=2.0)
        x = jax.random.normal(jax.random.PRNGKey(0), (1, t, d))
        cot = jax.random.normal(jax.random.PRNGKey(1), (t, d))
        v = moe.init(jax.random.PRNGKey(2), x)
        cap = held_capacity(t * k, count, z["experts"])
        assert cap < t * k, "a shape with one path shows nothing here"

        def ours(p, x, bias):
            y, st = moe.forward(p, dict(v["state"], router_bias=bias), x)
            return jnp.sum(y[0] * cot), (y[0], st["metrics"]["counters"])

        def plain(p, x, bias):
            idx, w = route_sigmoid_topk(x[0], p["w_router"], bias, k, 2.0)
            y = jnp.zeros_like(x[0])
            for e in range(count):
                w_e = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)
                g, u, dn = (p["experts"][n][e]
                            for n in ("w_gate", "w_up", "w_down"))
                y = y + w_e[:, None] * ((jax.nn.silu(x[0] @ g) * (x[0] @ u))
                                        @ dn)
            return jnp.sum(y * cot), (y, None)

        grad = lambda f: jax.jit(jax.grad(f, (0, 1), has_aux=True))
        bad = []
        # every token's k choices on held experts: T*k pairs, over any C
        collapsed = jnp.zeros((z["experts"],)).at[
            first:first + count].set(10.0)
        for path, bias in (("short", jnp.zeros((z["experts"],))),
                           ("whole", collapsed)):
            got, (y, m) = grad(ours)(v["params"], x, bias)
            with jax.default_matmul_precision("highest"):
                want, (y_ref, _) = grad(plain)(v["params"], x, bias)
            m = {n: int(c) for n, c in m.items()}
            took = "short" if m["moe.short_applies"] else "whole"
            self.say("expert_layer", f"path={path} took={took} rows={cap} "
                                     f"of {t * k} counters={json.dumps(m)}")
            if took != path or m["moe.dropped_pairs"] or m["moe.applies"] != 1:
                bad.append(f"{path}: counters")
            named = [("y", y, y_ref, TOL_MXU)] + [
                (jax.tree_util.keystr(kp), a, b, TOL_MXU_BWD)
                for (kp, a), b in zip(
                    jax.tree_util.tree_flatten_with_path(got)[0],
                    jax.tree_util.tree_leaves(want))]
            bad += [f"{path}: {name}" for name, a, b, tol in named
                    if not self.close("expert_layer",
                                      f"path={path} leaf={name}", a, b, tol)]
        if bad:
            raise AssertionError(f"expert layer off its reference: {bad}")

    # -- phases: train -------------------------------------------------------
    def _train(self, phase, model, x, y, method, steps, batch):
        """``Optimizer(...).optimize()`` for ``steps`` steps on one batch's
        worth of examples (every step sees the same examples, so a working
        step must drive the loss down).  Asserts the loss curve, the mesh and
        — on more than one device — where the state actually lives."""
        import jax

        from bigdl_tpu.data.dataset import DataSet
        from bigdl_tpu.nn.criterion import CrossEntropyCriterion
        from bigdl_tpu.optim.optimizer import Optimizer
        from bigdl_tpu.optim.trigger import Trigger
        from bigdl_tpu.runtime.engine import Engine

        logdir = tempfile.mkdtemp(prefix="chip_smoke_")
        try:
            opt = Optimizer(model, DataSet.array(x, y),
                            CrossEntropyCriterion(), batch_size=batch)
            opt.set_optim_method(method)
            opt.set_end_when(Trigger.max_iteration(steps))
            opt.set_train_summary(logdir)
            trained = opt.optimize()
            losses = [v for _, v in opt._train_summary.read_scalar("loss")]
            opt._train_summary.close()
        finally:
            shutil.rmtree(logdir, ignore_errors=True)
        self.say(phase, "losses=" + ",".join(f"{v:.4f}" for v in losses))
        assert len(losses) == steps, (len(losses), steps)
        assert np.isfinite(losses).all(), "non-finite loss"
        assert losses[-1] < losses[0], "loss did not fall"

        devices = jax.devices()
        mesh = Engine.get().mesh
        eng = trained._engine
        assert mesh.shape["data"] == len(devices) == eng.ndev, \
            (dict(mesh.shape), len(devices))
        placed = {"params": eng.flat_params,
                  "opt_state": jax.tree_util.tree_leaves(eng.opt_state)[0],
                  "batch": eng.shard_batch(x[:batch])}
        report = {}
        for name, arr in placed.items():
            shards = arr.addressable_shards
            assert {s.device for s in shards} == set(devices), \
                f"{name} lives on {sorted(str(s.device) for s in shards)}"
            report[name] = len({str(s.index) for s in shards})
        # params replicate; ZeRO-1 optimizer state and the batch split
        assert report["opt_state"] == report["batch"] == len(devices), report
        mem = [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]
        if self.dev["platform"] == "tpu":
            assert all(m and m > 0 for m in mem), f"bytes_in_use={mem}"
        self.say(phase, f"mesh={dict(mesh.shape)} distinct_shards={report} "
                        f"bytes_in_use={mem}")
        return trained

    def train_resnet50(self):
        from bigdl_tpu.models.resnet import resnet50
        from bigdl_tpu.optim.optim_method import SGD

        c = self.sz["resnet"]
        batch = c["batch_per_chip"] * self.dev["count"]
        rs = np.random.RandomState(0)
        x = rs.rand(batch, c["hw"], c["hw"], 3).astype(np.float32)
        y = rs.randint(0, c["classes"], (batch,)).astype(np.int32)
        self._train("train_resnet50",
                    resnet50(classes=c["classes"], stem="s2d"), x, y,
                    SGD(learning_rate=0.02, momentum=0.9), c["steps"], batch)

    def _lm_model(self):
        from bigdl_tpu.nn.attention import Transformer

        c = self.sz["lm"]
        return Transformer(vocab_size=c["vocab"], hidden_size=c["d"],
                           num_heads=c["heads"], ffn_size=4 * c["d"],
                           num_layers=c["layers"], dropout=0.0, mode="lm")

    def train_lm(self):
        from bigdl_tpu.optim.metrics import global_metrics
        from bigdl_tpu.optim.optim_method import Adam

        c = self.sz["lm"]
        batch = c["batch_per_chip"] * self.dev["count"]
        rs = np.random.RandomState(1)
        ids = rs.randint(2, c["vocab"], (batch, c["seq"] + 1)).astype(
            np.int32)
        model = self._lm_model()
        m = global_metrics()

        def flash_traces():
            return {k: v for k, v in m.counters.items()
                    if k.startswith("kernel.flash.traces")}

        before = flash_traces()
        trained = self._train("train_lm", model, ids[:, :-1], ids[:, 1:],
                              Adam(learning_rate=3e-4), c["steps"], batch)
        traced = {k: v - before.get(k, 0) for k, v in flash_traces().items()
                  if v > before.get(k, 0)}
        self.say("train_lm", f"flash_attention traces while tracing the "
                             f"step: {json.dumps(traced)} tile_share="
                             + json.dumps({k: v for k, v in m.gauges.items()
                                           if "flash.tile_share" in k}))
        if self.dev["platform"] == "tpu":
            assert any('direction="bwd"' in k for k in traced), \
                "the step never reached the flash backward kernels"
        self.lm = (model, trained.variables)

    # -- phase: serve ----------------------------------------------------------
    def serve_lm(self):
        import jax

        from bigdl_tpu.obs.attr import expected_compile, recompile_sentinel
        from bigdl_tpu.optim.metrics import global_metrics
        from bigdl_tpu.serving import (DecodeConfig, HttpClient, HttpFrontend,
                                       InferenceModel, ServingConfig,
                                       ServingServer)
        from bigdl_tpu.serving.decode_engine import DecodeRequest

        c, lmc = self.sz["serve"], self.sz["lm"]
        if self.lm is None:  # train_lm failed: still serve, from a fresh init
            model = self._lm_model()
            self.lm = (model, model.init(jax.random.PRNGKey(0),
                                         np.zeros((1, 8), np.int32)))
        model, variables = self.lm
        if self.dev["count"] > 1:
            # InferenceModel without layout= is single-device serving
            variables = jax.device_put(variables, jax.devices()[0])
            self.say("serve_lm", "one engine on one device "
                                 f"({jax.devices()[0]}) of "
                                 f"{self.dev['count']}")

        def build(use_flash):
            return InferenceModel(
                model, variables, batch_buckets=(1,),
                decode=DecodeConfig(
                    slots=c["slots"], page_size=c["page_size"],
                    pages_per_slot=c["pages_per_slot"],
                    prompt_chunk=c["prompt_chunk"],
                    max_new_tokens=c["max_new"], eos_id=1,
                    use_flash_decode=use_flash))

        sentinel = recompile_sentinel()
        m = global_metrics()
        # None = the engine's own choice, the kernel on TPU; the rehearsal
        # forces the (interpreted) kernel so the comparison below is real
        im = build(None if self.dev["platform"] == "tpu" else True)
        eng = im.decode_engine
        self.say("serve_lm", f"cap={eng.cfg.cap} "
                             f"length_buckets={eng.cfg.len_buckets()} "
                             f"flash_decode={eng._use_flash()}")
        if self.dev["platform"] == "tpu":
            assert eng._use_flash(), "TPU engine did not pick the kernel"
        t0 = time.perf_counter()
        im.warmup(np.zeros((8,), np.int32))  # raises if a program is refused
        self.say("serve_lm", f"warmup_seconds={time.perf_counter() - t0:.1f}")
        srv = ServingServer(im, ServingConfig(batch_size=4)).start()
        fe = HttpFrontend(srv, port=0, predict_timeout=300.0).start()
        ref_im = None
        try:
            rs = np.random.RandomState(2)
            lo, hi = c["prompt_lens"]
            prompts = [rs.randint(2, lmc["vocab"], (int(n),)).astype(np.int32)
                       for n in rs.randint(lo, hi, (c["requests"],))]
            compiles0 = m.counter("train.xla_compiles_total")
            sentinel.mark_steady()
            answers, errors = {}, {}

            def client(i):
                try:
                    answers[i] = HttpClient(fe.url, timeout=300.0).generate(
                        prompts[i], max_new_tokens=c["max_new"])
                except Exception as e:  # noqa: BLE001 — counted below
                    errors[i] = f"{type(e).__name__}: {e}"

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(len(prompts))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=600.0)
            hung = sum(t.is_alive() for t in threads)
            compiled = m.counter("train.xla_compiles_total") - compiles0
            unexpected = m.counter("train.unexpected_recompiles_total")
            sentinel.mark_warmup()
            buckets = sorted({eng.cfg.bucket_pages(len(p) + c["max_new"])
                              for p in prompts})
            self.say("serve_lm",
                     f"requests={len(prompts)} answered={len(answers)} "
                     f"errors={len(errors)} hung={hung} "
                     f"prompt_lens={[len(p) for p in prompts]} "
                     f"buckets_reached={buckets} "
                     f"compiles_after_warmup={compiled:.0f} "
                     f"unexpected_recompiles={unexpected:.0f} "
                     f"engine_stats={eng.stats}")
            assert not errors, errors
            assert hung == 0
            assert all(len(answers[i]) >= 1 for i in range(len(prompts)))
            assert len(buckets) > 1, "only one length bucket was exercised"
            assert compiled == 0 and unexpected == 0

            # kernel path vs the plain jnp path (use_flash_decode=False):
            # the shortest and the longest prompt, greedy, through both
            # engines.  Same tokens (also as HTTP answered them) and summed
            # log-prob within TOL_LOGP_NATS per token.  Seeds are fixed, so
            # a near-tied argmax that flips under a numerics change fails
            # here every time, not sometimes — look at the printed margin.
            with expected_compile():
                ref_im = build(False)
                for i in (int(j) for j in np.argsort(
                        [len(p) for p in prompts])[[0, -1]]):
                    rk, rj = (e.submit(DecodeRequest(
                        tokens=prompts[i], max_new_tokens=c["max_new"])
                    ).wait(timeout=600.0)
                        for e in (eng, ref_im.decode_engine))
                    per_token = abs(rk.logp - rj.logp) / len(rj.tokens)
                    self.say("serve_lm",
                             f"prompt_len={len(prompts[i])} kernel_vs_jnp "
                             f"tokens={rk.tokens.tolist()} vs "
                             f"{rj.tokens.tolist()} logp={rk.logp:.4f}/"
                             f"{rj.logp:.4f} per_token_abs_diff="
                             f"{per_token:.1e} tol={TOL_LOGP_NATS}")
                    assert np.array_equal(rk.tokens, rj.tokens), \
                        "kernel and jnp paths chose different tokens"
                    assert np.array_equal(rk.tokens, answers[i]), \
                        "HTTP answer differs from the engine's own"
                    assert per_token <= TOL_LOGP_NATS, per_token
        finally:
            fe.stop()
            srv.stop()
            eng.stop()
            if ref_im is not None:
                ref_im.decode_engine.stop()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny shapes on the CPU backend; a harness check "
                         "that never prints the chip result")
    args = ap.parse_args(argv)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"  # read when jax is imported

    # stay off the backend until the compile cache is placed
    from bigdl_tpu.runtime.engine import enable_compile_cache

    cache_dir = enable_compile_cache()

    import jax

    cache = {"requests": 0, "hits": 0}

    def on_cache_event(event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            cache["requests"] += 1
        elif event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1

    jax.monitoring.register_event_listener(on_cache_event)

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != ("cpu" if args.rehearse_cpu else "tpu"):
        print(f"chip_smoke: default backend is {device['platform']!r} "
              f"({device['kind']}), not a TPU; nothing was built and there "
              "is no result", file=sys.stderr)
        return 2

    from bigdl_tpu.native import lib as native
    from bigdl_tpu.obs.attr import recompile_sentinel
    from bigdl_tpu.optim.metrics import global_metrics

    recompile_sentinel()  # counts every backend compile from here on
    smoke = Smoke(TINY if args.rehearse_cpu else FULL, device)
    smoke.say("setup", f"jax={jax.__version__} compile_cache={cache_dir} "
                       f"native_lib_in_use={native.available()}")
    t0 = time.perf_counter()
    for phase in ("kernels", "expert_layer", "train_resnet50", "train_lm",
                  "serve_lm"):
        smoke.run(phase, getattr(smoke, phase))
    m = global_metrics()
    compile_hist = m.hists.get("train.compile_time_s")
    smoke.say("summary",
              f"compiles={m.counter('train.xla_compiles_total'):.0f} "
              f"compile_seconds={compile_hist.sum if compile_hist else 0:.1f} "
              f"persistent_cache_hits={cache['hits']}/{cache['requests']} "
              f"wall_seconds={time.perf_counter() - t0:.1f} "
              + " ".join(f"{k}={'PASS' if v else 'FAIL'}"
                         for k, v in smoke.results.items()))
    ok = all(smoke.results.values())
    if args.rehearse_cpu:
        print("[chip_smoke] rehearsal on platform=cpu "
              f"{'passed' if ok else 'FAILED'}: not a chip result")
        return 0 if ok else 1
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
