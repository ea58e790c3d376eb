"""Time the candidates for the expert FFN's grouped matmuls on the chip.

    chiprun -- python3 tools/moe_gmm_bench.py            # the whole stack
    chiprun -- python3 tools/moe_gmm_bench.py candidates # PR 26's sweep
    chiprun -- python3 tools/moe_gmm_bench.py tilings    # PR 40's sweep
    chiprun -- python3 tools/moe_gmm_bench.py tilings 1024 12288  # row tiles
    chiprun -- python3 tools/moe_gmm_bench.py chosen     # PR 41's reading
    chiprun -- python3 tools/moe_gmm_bench.py wide       # PR 50's reading

**The whole stack (PR 37).** A decode step's 256 rows (32 slots, top-8)
through one layer's three grouped matmuls, at both expert cells' shapes
(OLMoE: L 3, 64 experts of 2048 x 1024; Mellum2: L 8, 2304 x 896), the
weights in bfloat16 as an engine holds them:

- ``layer_buffer``: the weights are one layer's ``[E, D, F]`` buffer: the
  call a layer scan makes AFTER it has copied the layer's slice out;
- ``whole_stack@first`` / ``@last``: the weights are the stack
  ``[L*E, D, F]`` and ``group_sizes`` is zero but for the layer's E
  entries at ``l*E`` (what ``dropless_expert_ffn(first_expert=l*E)``
  hands ``ragged_dot``), at the first and the last layer's offset;
- ``gmm_whole_stack@...``: jax's Pallas ``megablox.gmm`` on the same
  operands (the fallback ISSUE 37 names);
- ``scan_sliced`` / ``scan_whole``: a ``lax.scan`` over the L layers, the
  stacks handed as ``xs`` (the copy a layer and stack included) or closed
  over whole, in ms a LAYER: what the serving programs pay.

**``candidates``**: OLMoE's shapes at rows 256 and 2,048 / 12,288
(prefill of 256 / 1,536 tokens), ``jax.lax.ragged_dot`` against
``megablox.gmm`` at three tilings, each with the weights stored in
bfloat16 and in float32 cast inside the timed program. PERF.md (PR 26)
holds the readings that chose ``ragged_dot``.

**``tilings`` (PR 40)**: the Pallas grouped matmul at a sweep of (rows,
k, n) tiles, ONE call at a time (``gate_up``: ``[m, D] @ [L*E, D, F]``;
``down``: ``[m, F] @ [L*E, F, D]``) on the whole stack with the layer's
groups at the LAST layer's offset, at both cells' shapes, m = 256 (a
decode step) and the cell's prefill rows (Mellum2's 512-token chunk =
4,096; OLMoE's 256-token prompt = 2,048); then the layer's three calls
together (``ffn``) at the first and the last layer, on ``ragged_dot``,
on the compiler's own tiling and on ``ops.moe_dispatch.gmm_tiling``'s
choice. Every line carries the grid steps the routed sizes give at that
tiling and ``gmm_vmem_bytes``' reckoning; ``chosen`` marks the rows at
``gmm_tiling``'s own choice. A tiling the compiler refuses is a line
with ``error``. With rows named after ``tilings`` (multiples of 256),
those rows in place of the cells', and the ROW tiles alone at
``gmm_tiling``'s (k, n). The lines go to
``chiprun_out/moe_gmm_tilings.jsonl`` too.

**``chosen`` (PR 41)**: what ``ops.moe_dispatch.grouped_matmul_impl``
picks at a width it was not tuned on, as a reading and no more: 128
experts of 2048 x 768 (kanana-2-30b-a3b-d5: 4 expert layers, top-6), a
decode step's 192 rows and a 512-token chunk's 3,072, the layer's three
calls on the whole stack at the last layer's offset, on the resolver's
choice and on ``ragged_dot``.

**``wide`` (PR 50)**: ``chosen`` at 64 experts of 3584 x 1024
(xing4.0-29b-a4b-d5: 4 expert layers, top-4), a decode step's 128 rows
and a 512-token chunk's 2,048. 3,584 = 7 x 512, so XLA's own tiling is
512 x 512, fourteen tiles an expert; the resolver takes the Pallas
kernel at ``gmm_tiling``'s two ((128, 3584, 512) and (128, 1024, 1792)
at 128 rows: legal though 28 lane tiles are no power of two). The line
also times the kernel at ``gmm_tiling``'s choice whatever the resolver
says (``ms_gmm_tiling``): it was the reading that first moved the rule
(1.689 for 2.074 ms at 128 rows, 2.374 for 4.912 at 2,048; since PR 53
the rule is the kernel wherever a tiling exists).

**``held`` (PR 43)**: an expert layer that HOLDS A SHARE of its router's
experts (deepseek-v3.2-d5: 16 of 256 experts of 7168 x 2048, top-8, 4
expert layers): a decode step's 128 assignments (16 slots), of which ~8
land on held experts and the rest belong to no group, and a 512-token
chunk's 4,096, of which ~256 land; the layer's three calls on the whole
stack of 4 x 16 groups at the last layer's offset, on the resolver's
choice and on ``ragged_dot``: what ``grouped_matmul_impl`` picks at this
width and this few rows, as a reading and no more.

Prints one JSON line per reading.
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

E, D, F, K = 64, 2048, 1024, 8
# the two expert cells: (name, layers, hidden, one expert's width, the
# tiling the v5e compiler prints for ``ragged_dot`` at these shapes)
STACKS = (("olmoe-1b-7b-d3", 3, 2048, 1024, (256, 512, 512)),
          ("mellum2-12b-a2.5b-d8", 8, 2304, 896, (256, 256, 128)))


def swiglu(grouped, xs, wg, wu, wd, sizes):
    act = jax.nn.silu(grouped(xs, wg, sizes)) * grouped(xs, wu, sizes)
    return grouped(act.astype(jnp.bfloat16), wd, sizes)


def ffn(grouped):
    def run(xs, wg, wu, wd, sizes):
        wg, wu, wd = (w.astype(jnp.bfloat16) for w in (wg, wu, wd))
        return swiglu(grouped, xs, wg, wu, wd, sizes)
    return jax.jit(run)


def ragged(xs, w, sizes):
    return jax.lax.ragged_dot(xs, w, sizes,
                              preferred_element_type=jnp.bfloat16)


def megablox(tiling):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    def run(xs, w, sizes):
        tm, tk, tn = tiling
        return gmm(xs, w, sizes, jnp.bfloat16,
                   (min(tm, xs.shape[0]), min(tk, w.shape[1]),
                    min(tn, w.shape[2])))
    return run


def timed(fn, args, reps=200, rounds=5):
    """ms a call with ``reps`` calls in the device's queue at once: a
    half-millisecond call is shorter than a dispatch and its wake-up, so
    a clock around ONE call (PR 26's readings) reads the host too."""
    jax.block_until_ready(fn(*args))
    out = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        for _ in range(reps):
            last = fn(*args)
        jax.block_until_ready(last)
        out.append((time.perf_counter() - t0) / reps)
    return 1e3 * float(np.median(out))


def grid_steps(sizes, tiling, k, n):
    """Grid steps of a grouped-matmul call: n tiles x k tiles x the
    (group, row tile) pairs that hold a row."""
    tm, tk, tn = tiling
    ends = np.cumsum(sizes)
    starts = ends - sizes
    visits = int(sum((e - 1) // tm - s // tm + 1
                     for s, e in zip(starts, ends) if e > s))
    return -(-n // tn) * -(-k // tk) * visits


def routed_sizes(rng, rows):
    idx = np.stack([rng.permutation(E)[:K] for _ in range(rows // K)])
    return np.bincount(idx.ravel(), minlength=E).astype(np.int32)


def whole_stack(rows=256):
    rng = np.random.default_rng(0)
    for name, L, d, f, tiling in STACKS:
        keys = jax.random.split(jax.random.key(0), 3)
        # [L, E, ., .] as ``params["layers"]`` holds them
        wg, wu, wd = (jax.random.normal(k, s, jnp.bfloat16) * 0.02
                      for k, s in zip(keys, ((L, E, d, f), (L, E, d, f),
                                             (L, E, f, d))))
        merged = tuple(w.reshape((L * E,) + w.shape[2:])
                       for w in (wg, wu, wd))
        sizes = jnp.asarray(routed_sizes(rng, rows))
        xs = jnp.asarray(rng.normal(size=(rows, d)), jnp.bfloat16)

        def padded(first):
            return jax.lax.dynamic_update_slice(
                jnp.zeros((L * E,), jnp.int32), sizes, (first,))

        def one_layer(grouped):
            return jax.jit(lambda xs, wg, wu, wd, first: swiglu(
                grouped, xs, wg, wu, wd, padded(first)))

        def scan_sliced(xs, wg, wu, wd):
            def body(x, w):
                return swiglu(ragged, x, *w, sizes), None
            return jax.lax.scan(body, xs, (wg, wu, wd))[0]

        def scan_whole(xs, wg, wu, wd):
            def body(x, l):
                return swiglu(ragged, x, wg, wu, wd, padded(l * E)), None
            return jax.lax.scan(body, xs, jnp.arange(L, dtype=jnp.int32))[0]

        buffers = tuple(w[L - 1] for w in (wg, wu, wd))
        want = np.asarray(ffn(ragged)(xs, *buffers, sizes), np.float32)
        readings = [("layer_buffer", ffn(ragged), (xs, *buffers, sizes), 1)]
        for impl, grouped in (("whole_stack", ragged),
                              ("gmm_whole_stack", megablox(tiling))):
            for at, l in (("first", 0), ("last", L - 1)):
                readings.append((f"{impl}@{at}", one_layer(grouped),
                                 (xs, *merged, jnp.int32(l * E)), 1))
        readings += [("scan_sliced", jax.jit(scan_sliced),
                      (xs, wg, wu, wd), L),
                     ("scan_whole", jax.jit(scan_whole), (xs, *merged), L)]
        for impl, fn, args, calls in readings:
            line = {"shapes": name, "groups": L * E, "rows": rows,
                    "impl": impl}
            try:
                line["ms"] = timed(fn, args) / calls
                if impl.endswith("@last"):
                    got = np.asarray(fn(*args), np.float32)
                    line["max_abs_vs_layer_buffer"] = float(
                        np.abs(got - want).max())
            except Exception as e:     # noqa: BLE001 — a refusal is a reading
                line["error"] = f"{type(e).__name__}: {str(e)[:200]}"
            print(json.dumps(line), flush=True)
        del wg, wu, wd, merged, buffers, readings


def candidates():
    rng = np.random.default_rng(0)
    keys = jax.random.split(jax.random.key(0), 3)
    w32 = [jax.random.normal(k, s, jnp.float32) * 0.02 for k, s in
           zip(keys, ((E, D, F), (E, D, F), (E, F, D)))]
    w16 = [w.astype(jnp.bfloat16) for w in w32]
    cands = {"ragged_dot": ragged,
             "gmm_128_128_128": megablox((128, 128, 128)),
             "gmm_256_2048_512": megablox((256, 2048, 512)),
             "gmm_512_1024_1024": megablox((512, 1024, 1024))}
    for rows in (256, 2048, 12288):
        sizes = jnp.asarray(routed_sizes(rng, rows))
        xs = jnp.asarray(rng.normal(size=(rows, D)), jnp.bfloat16)
        want = None
        for name, grouped in cands.items():
            for stored, ws in (("bf16", w16), ("f32", w32)):
                try:
                    fn = ffn(grouped)
                    ms = timed(fn, (xs, *ws, sizes), reps=50)
                    got = np.asarray(fn(xs, *ws, sizes), np.float32)
                    want = got if want is None else want
                    err = float(np.abs(got - want).max())
                    line = {"rows": rows, "impl": name, "stored": stored,
                            "ms": ms, "max_abs_vs_first": err}
                except Exception as e:     # noqa: BLE001 — a refusal is a reading
                    line = {"rows": rows, "impl": name, "stored": stored,
                            "error": f"{type(e).__name__}: {str(e)[:200]}"}
                print(json.dumps(line), flush=True)


# the cells' prefill rows: tokens x top-8
PREFILL_ROWS = {"olmoe-1b-7b-d3": 2048, "mellum2-12b-a2.5b-d8": 4096}


def tilings(rows_asked=(), out_path="chiprun_out/moe_gmm_tilings.jsonl"):
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as out:
        sweep_tilings(rows_asked, out)


def sweep_tilings(rows_asked, out):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from ray_tpu.ops.moe_dispatch import (GMM_VMEM_BUDGET, gmm_tiling,
                                          gmm_vmem_bytes, lane_divisors)

    def reading(line, fn, args, reps):
        try:
            line["ms"] = timed(fn, args, reps=reps, rounds=3)
        except Exception as e:     # noqa: BLE001 — a refusal is a reading
            line["error"] = f"{type(e).__name__}: {str(e)[:160]}"
        text = json.dumps(line)
        print(text, flush=True)
        out.write(text + "\n")
        out.flush()

    rng = np.random.default_rng(0)
    for name, L, d, f, compilers in STACKS:
        keys = jax.random.split(jax.random.key(0), 3)
        merged = tuple(jax.random.normal(key, shape, jnp.bfloat16) * 0.02
                       for key, shape in zip(keys, ((L * E, d, f),
                                                    (L * E, d, f),
                                                    (L * E, f, d))))
        for rows in rows_asked or (256, PREFILL_ROWS[name]):
            sizes = routed_sizes(rng, rows)
            reps = 200 if rows <= 512 else 50

            def padded(first, sizes=sizes):
                return jax.lax.dynamic_update_slice(
                    jnp.zeros((L * E,), jnp.int32), jnp.asarray(sizes),
                    (first,))

            last = jnp.int32((L - 1) * E)
            tms = ((32, 64, 128, 256) if rows <= 512 else (128, 256, 512))
            for call, k, n, w in (("gate_up", d, f, merged[0]),
                                  ("down", f, d, merged[2])):
                xs = jnp.asarray(rng.normal(size=(rows, k)), jnp.bfloat16)
                chosen = gmm_tiling(rows, k, n, 2)
                if rows_asked:      # the row tiles alone, at the chosen (k, n)
                    sweep = {(tm, *chosen[1:]) for tm in tms if rows % tm == 0}
                else:
                    sweep = {(tm, tk, tn) for tm in tms
                             for tk in lane_divisors(k) if tk >= 256
                             for tn in lane_divisors(n) if tn >= 256
                             if gmm_vmem_bytes(tm, tk, tn, 2)
                             <= GMM_VMEM_BUDGET + 2 * 2**20}
                sweep |= {compilers, chosen}
                base = {"shapes": name, "groups": L * E, "rows": rows,
                        "call": call, "k": k, "n": n, "at": "last"}
                reading({**base, "impl": "ragged_dot"},
                        jax.jit(lambda xs, w, first: ragged(
                            xs, w, padded(first))), (xs, w, last), reps)
                for tiling in sorted(sweep):
                    reading({**base, "impl": "gmm", "tiling": list(tiling),
                             "grid_steps": grid_steps(sizes, tiling, k, n),
                             "vmem_bytes": gmm_vmem_bytes(*tiling, 2),
                             "chosen": tiling == chosen},
                            jax.jit(lambda xs, w, first, t=tiling: megablox(t)(
                                xs, w, padded(first))), (xs, w, last), reps)
            # the layer's three calls together, at both ends of the stack
            xs = jnp.asarray(rng.normal(size=(rows, d)), jnp.bfloat16)

            def by_shape(xs, w, sizes):
                return megablox(gmm_tiling(xs.shape[0], *w.shape[1:], 2))(
                    xs, w, sizes)

            for impl, grouped in (("ragged_dot", ragged),
                                  ("gmm_compilers", megablox(compilers)),
                                  ("gmm_chosen", by_shape)):
                fn = jax.jit(lambda xs, wg, wu, wd, first, g=grouped: swiglu(
                    g, xs, wg, wu, wd, padded(first)))
                for at, l in (("first", 0), ("last", L - 1)):
                    reading({"shapes": name, "groups": L * E, "rows": rows,
                             "call": "ffn", "at": at, "impl": impl},
                            fn, (xs, *merged, jnp.int32(l * E)), reps)
        del merged


def chosen(name="kanana-2-30b-a3b-d5", L=4, experts=128, d=2048, f=768,
           top_k=6, rows_asked=(192, 3072), with_gmm_tiling=False):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))    # the one mode that asks the program
    from ray_tpu.ops.moe_dispatch import (gmm_tiling, gmm_vmem_bytes,
                                          grouped_matmul,
                                          grouped_matmul_impl)

    rng = np.random.default_rng(0)
    keys = jax.random.split(jax.random.key(0), 3)
    wg, wu, wd = (jax.random.normal(k, (L * experts,) + shape, jnp.bfloat16)
                  * 0.02 for k, shape in zip(keys, ((d, f), (d, f), (f, d))))
    for rows in rows_asked:
        idx = np.stack([rng.permutation(experts)[:top_k]
                        for _ in range(rows // top_k)])
        sizes = np.zeros(L * experts, np.int32)
        sizes[(L - 1) * experts:] = np.bincount(idx.ravel(),
                                                minlength=experts)
        xs = jax.random.normal(jax.random.key(rows), (rows, d), jnp.bfloat16)
        picks = {call: grouped_matmul_impl(rows, k, n, 2)
                 for call, (k, n) in (("gate_up", (d, f)), ("down", (f, d)))}
        line = {"shapes": name, "groups": L * experts, "rows": rows,
                "experts_with_rows": int((sizes > 0).sum()), "call": "ffn",
                "at": "last"}
        for call, (impl, tiling) in picks.items():
            line[call] = {"impl": impl, "tiling": tiling, "vmem_bytes": (
                tiling and gmm_vmem_bytes(*tiling, 2))}
        reps = 200 if rows <= 256 else 50
        impls = [("resolver", lambda a, w, s: grouped_matmul(
            a, w, s, jnp.bfloat16)), ("ragged_dot", ragged)]
        if with_gmm_tiling:
            impls.append(("gmm_tiling", lambda a, w, s: megablox(
                gmm_tiling(a.shape[0], *w.shape[1:]))(a, w, s)))
        for impl, grouped in impls:
            line["ms_" + impl] = timed(ffn(grouped),
                                       (xs, wg, wu, wd, jnp.asarray(sizes)),
                                       reps)
        print(json.dumps(line), flush=True)


def held(name="deepseek-v3.2-d5", L=4, router=256, n_held=16, d=7168, f=2048,
         top_k=8, rows_asked=(128, 4096)):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from ray_tpu.ops.moe_dispatch import grouped_matmul, grouped_matmul_impl

    rng = np.random.default_rng(0)
    keys = jax.random.split(jax.random.key(0), 3)
    wg, wu, wd = (jax.random.normal(k, (L * n_held,) + shape, jnp.bfloat16)
                  * 0.02 for k, shape in zip(keys, ((d, f), (d, f), (f, d))))
    for rows in rows_asked:
        idx = np.stack([rng.permutation(router)[:top_k]
                        for _ in range(rows // top_k)]).ravel()
        sizes = np.zeros(L * n_held, np.int32)
        sizes[(L - 1) * n_held:] = np.bincount(idx[idx < n_held],
                                               minlength=n_held)
        xs = jax.random.normal(jax.random.key(rows), (rows, d), jnp.bfloat16)
        line = {"shapes": name, "groups": L * n_held, "rows": rows,
                "rows_in_a_group": int(sizes.sum()),
                "experts_with_rows": int((sizes > 0).sum()), "call": "ffn",
                "at": "last"}
        for call, (k, n) in (("gate_up", (d, f)), ("down", (f, d))):
            impl, tiling = grouped_matmul_impl(rows, k, n, 2)
            line[call] = {"impl": impl, "tiling": tiling}
        reps = 200 if rows <= 256 else 50
        for impl, grouped in (
                ("resolver", lambda a, w, s: grouped_matmul(
                    a, w, s, jnp.bfloat16)), ("ragged_dot", ragged)):
            line["ms_" + impl] = timed(ffn(grouped),
                                       (xs, wg, wu, wd, jnp.asarray(sizes)),
                                       reps)
        print(json.dumps(line), flush=True)


def latent(name="nemotron-3-super-d11", L=5, router=512, n_held=128,
           latent_dim=1024, f=2688, top_k=22, rows_asked=(1408, 11264)):
    """**``latent``**: the gate-less relu^2 experts in a latent
    (``nemotron-3-super-d11``: 5 x 128 held groups, 1,024 x 2,688 =
    21 x 128 and its transpose, top-22 of 512) at a decode step's 1,408
    rows and a prefill chunk's 11,264, groups at the LAST layer's
    offset: the resolver's tiling (k is split where the whole expert
    does not fit: 128 x 512 x 2688) against an n-split of the same
    VMEM class and against ``ragged_dot``."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from ray_tpu.ops.moe_dispatch import grouped_matmul, grouped_matmul_impl

    rng = np.random.default_rng(0)
    keys = jax.random.split(jax.random.key(0), 2)
    w1, w2 = (jax.random.normal(k, (L * n_held,) + shape, jnp.bfloat16)
              * 0.02 for k, shape in zip(keys, ((latent_dim, f),
                                                (f, latent_dim))))

    def pair(grouped):
        def run(xs, w1, w2, sizes):
            act = jnp.square(jax.nn.relu(grouped(xs, w1, sizes)))
            return grouped(act.astype(jnp.bfloat16), w2, sizes)
        return jax.jit(run)

    for rows in rows_asked:
        idx = np.stack([rng.permutation(router)[:top_k]
                        for _ in range(rows // top_k)]).ravel()
        sizes = np.zeros(L * n_held, np.int32)
        sizes[(L - 1) * n_held:] = np.bincount(idx[idx < n_held],
                                               minlength=n_held)
        xs = jax.random.normal(jax.random.key(rows), (rows, latent_dim),
                               jnp.bfloat16)
        line = {"shapes": name, "groups": L * n_held, "rows": rows,
                "rows_in_a_group": int(sizes.sum()),
                "experts_with_rows": int((sizes > 0).sum()), "call": "pair",
                "at": "last"}
        for call, (k, n) in (("up", (latent_dim, f)), ("down", (f, latent_dim))):
            impl, tiling = grouped_matmul_impl(rows, k, n, 2)
            line[call] = {"impl": impl, "tiling": tiling}
        tm = line["up"]["tiling"][0] if line["up"]["tiling"] else 128
        reps = 200 if rows <= 2048 else 30
        for impl, grouped in (
                ("resolver", lambda a, w, s: grouped_matmul(
                    a, w, s, jnp.bfloat16)),
                ("n_split", lambda a, w, s: megablox(
                    (tm, 1024, 896) if w.shape[1] == latent_dim
                    else (tm, 896, 1024))(a, w, s)),
                ("ragged_dot", ragged)):
            try:
                line["ms_" + impl] = timed(
                    pair(grouped), (xs, w1, w2, jnp.asarray(sizes)), reps)
            except Exception as e:          # noqa: BLE001 - a reading
                line["ms_" + impl] = f"{type(e).__name__}: {str(e)[:160]}"
        print(json.dumps(line), flush=True)


def main():
    if jax.devices()[0].platform != "tpu":
        sys.exit("moe_gmm_bench: no TPU; a CPU time is not a reading")
    if sys.argv[1:] == ["candidates"]:
        candidates()
    elif sys.argv[1:2] == ["tilings"]:
        tilings(tuple(int(r) for r in sys.argv[2:]))
    elif sys.argv[1:] == ["chosen"]:
        chosen()
    elif sys.argv[1:] == ["wide"]:
        chosen("xing4.0-29b-a4b-d5", 4, 64, 3584, 1024, 4, (128, 2048),
               with_gmm_tiling=True)
    elif sys.argv[1:] == ["held"]:
        held()
    elif sys.argv[1:] == ["latent"]:
        latent()
    else:
        whole_stack()


if __name__ == "__main__":
    main()
