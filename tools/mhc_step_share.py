"""What the residual streams cost a decode step, on the chip.

    chiprun -- python3 tools/mhc_step_share.py

The cell's own decode program (``decode_step_paged`` of xing4.0-29b-a4b-d5
at 32 slots x 16,384, every slot 10,000 rows deep, seeded weights, the pool
donated), ms a step over 200 steps in the device's queue, as the
configuration states it (four streams) and with ``hc_mult`` 1 (the plain
residual, every other number the same): the difference is what ten
sublayers' maps, reads and writes cost a step. The benchmark's trace cannot
give this number: the streams' work is XLA's fusions (``ops/mhc.py`` says
why no kernel), a fusion's name says nothing of what it holds, and
``benchmark/lib/trace.py`` reduces by name (PERF.md section 7). One JSON
line, to ``chiprun_out/mhc_step_share.json`` too.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    if jax.devices()[0].platform != "tpu":
        sys.exit("mhc_step_share: no TPU; a CPU time is not a reading")
    from benchmark import run as harness
    from benchmark.builders import xing as builder
    from ray_tpu.models import model_for

    cfg = harness.load_json(harness.ROOT,
                            "benchmark/configs/xing4.0-29b-a4b-d5.json")
    B, bs, maxb, deep = 32, 32, 512, 10_000
    streams = builder.program_config(cfg, maxb * bs)
    tables = jnp.arange(B * maxb, dtype=jnp.int32).reshape(B, maxb)
    tokens = jnp.arange(B, dtype=jnp.int32) + 7
    offsets = jnp.full((B,), deep, jnp.int32)
    line = {"program": "decode_step_paged", "slots": B, "rows_deep": deep}
    for name, n in (("four_streams", streams.hc_mult), ("one_stream", 1)) * 2:
        model = model_for(dataclasses.replace(streams, hc_mult=n))
        params = jax.jit(lambda k: model.serving_params(model.init(k)))(
            jax.random.key(0))
        step = jax.jit(model.decode_step_paged, donate_argnums=(2,))
        pool = model.init_kv_pool(B * maxb + 1, bs)
        for _ in range(3):
            logits, pool = step(params, tokens, pool, tables, offsets)
        jax.block_until_ready(logits)
        rounds = []
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(200):
                logits, pool = step(params, tokens, pool, tables, offsets)
            jax.block_until_ready(logits)
            rounds.append((time.perf_counter() - t0) / 200)
        line.setdefault("ms_a_step_" + name, []).append(
            1e3 * float(np.median(rounds)))
        del pool, step, params, model
    line["streams_ms_a_step"] = (min(line["ms_a_step_four_streams"])
                                 - min(line["ms_a_step_one_stream"]))
    line["streams_share_of_step_pct"] = (
        100 * line["streams_ms_a_step"] / min(line["ms_a_step_four_streams"]))
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/mhc_step_share.json", "w") as out:
        out.write(json.dumps(line) + "\n")
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
