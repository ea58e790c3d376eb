"""Does a serve cell's decode program fit the chip? No chip needed.

    JAX_PLATFORMS=cpu python3 -m benchmark.aot_fit <cell> [max_seq]

Compiles the decode program of a ``serve_closed`` / ``serve_open`` cell
as the engine jits it (the model's ``decode_step_paged`` with the pool
donated; an expert model's counted step), at the published widths and
the traffic file's engine shape, for a DESCRIBED v5e (the TPU's compiler
is installed where no TPU is). Prints the compiler's memory analysis as
one JSON line: GiB of arguments (weights + pool), of temporaries, and
their sum against the chip's 15.75 GiB. A program that does not fit
raises what the chip's compiler would raise. Nothing runs: this is a
size, never a time, and a size that passes here is still to be run on
the chip (what else the process keeps there is not in it).

This process's backend is the CPU, where the model rightly takes the XLA
reference for paged attention; the tool tells it what it would see on
the chip (``tests/test_chip_smoke.py`` does the same).
"""

from __future__ import annotations

import importlib
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

CHIP_GIB = 15.75              # what a v5e's compiler has to place a program in


def decode_memory(cell: str, max_seq: int | None = None) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.ops import paged_attention

    from benchmark import run as harness

    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    w = next(w for w in bench["workloads"] if w["name"] == cell)
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    cfg = harness.load_json(harness.ROOT, conf["file"])
    eng = harness.load_json(harness.HERE, "traffic",
                            w["traffic"] + ".json")["engine"]
    max_seq = int(max_seq or eng["max_seq"])
    B, bs = eng["max_slots"], eng["block_size"]
    maxb = max_seq // bs

    paged_attention.on_chip = lambda: True
    paged_attention.pallas_interpret = lambda: False
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def placed(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=chip), tree)

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip)

    model = importlib.import_module(
        "benchmark.builders." + cfg["builder"]).build_model(cfg, max_seq)
    params = placed(jax.eval_shape(model.init, jax.random.key(0)))
    pool = placed(jax.eval_shape(
        lambda: model.init_kv_pool(B * maxb + 1, bs)))
    args = [params, ints(B), pool, ints(B, maxb), ints(B)]
    step = model.decode_step_paged
    load_shape = model.ffn_load_shape()
    if load_shape is not None:        # the engine's counted step

        def step(params, tokens, pool, tables, offsets, load):
            logits, pool, extras = model.decode_step_paged_counted(
                params, tokens, pool, tables, offsets,
                tables[:, 0] != B * maxb)
            return logits, pool, load + extras["load"]

        args.append(ints(*load_shape))
    compiled = jax.jit(step, donate_argnums=(2,)).lower(*args).compile()
    mem = compiled.memory_analysis()

    def gib(tree):
        return sum(a.size * a.dtype.itemsize
                   for a in jax.tree.leaves(tree)) / 2**30

    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    return {"cell": cell, "slots": B, "max_seq": max_seq, "block_size": bs,
            "attention": model.paged_decode_impl(),
            "mosaic_calls": compiled.as_text().count("tpu_custom_call"),
            "weights_gib": gib(params), "pool_gib": gib(pool),
            "arguments_gib": mem.argument_size_in_bytes / 2**30,
            "temporaries_gib": mem.temp_size_in_bytes / 2**30,
            "outputs_not_aliased_gib": (mem.output_size_in_bytes
                                        - mem.alias_size_in_bytes) / 2**30,
            "total_gib": total / 2**30, "chip_gib": CHIP_GIB,
            "spare_gib": CHIP_GIB - total / 2**30}


if __name__ == "__main__":
    print(json.dumps(decode_memory(
        sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else None)))
