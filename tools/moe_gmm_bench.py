"""Time the candidates for the expert FFN's grouped matmuls on the chip.

    chiprun -- python3 tools/moe_gmm_bench.py

OLMoE-1B-7B's shapes (64 experts of 2048 x 1024, top-8): rows T*K = 256
(a 32-slot decode step) and 2,048 / 12,288 (prefill of 256 / 1,536
tokens), rows sorted by expert. Candidates: ``jax.lax.ragged_dot`` and
the Pallas ``megablox.gmm`` shipped with jax, each with the weights
stored in bfloat16 and, as the program stores them, in float32 cast
inside the timed program. Prints one JSON line per (rows, candidate);
PERF.md (PR 26) holds the readings that chose ``ragged_dot``.
"""

from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

E, D, F, K = 64, 2048, 1024, 8


def ffn(grouped):
    def run(xs, wg, wu, wd, sizes):
        wg, wu, wd = (w.astype(jnp.bfloat16) for w in (wg, wu, wd))
        act = jax.nn.silu(grouped(xs, wg, sizes)) * grouped(xs, wu, sizes)
        return grouped(act.astype(jnp.bfloat16), wd, sizes)
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


def timed(fn, args, reps=20):
    jax.block_until_ready(fn(*args))
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        out.append(time.perf_counter() - t0)
    return 1e3 * float(np.median(out))


def main():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit("moe_gmm_bench: no TPU; a CPU time is not a reading")
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
        idx = np.stack([rng.permutation(E)[:K] for _ in range(rows // K)])
        sizes = jnp.asarray(np.bincount(idx.ravel(), minlength=E), jnp.int32)
        xs = jnp.asarray(rng.normal(size=(rows, D)), jnp.bfloat16)
        want = None
        for name, grouped in cands.items():
            for stored, ws in (("bf16", w16), ("f32", w32)):
                try:
                    fn = ffn(grouped)
                    ms = timed(fn, (xs, *ws, sizes))
                    got = np.asarray(fn(xs, *ws, sizes), np.float32)
                    want = got if want is None else want
                    err = float(np.abs(got - want).max())
                    line = {"rows": rows, "impl": name, "stored": stored,
                            "ms": ms, "max_abs_vs_first": err}
                except Exception as e:     # noqa: BLE001 — a refusal is a reading
                    line = {"rows": rows, "impl": name, "stored": stored,
                            "error": f"{type(e).__name__}: {str(e)[:200]}"}
                print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
