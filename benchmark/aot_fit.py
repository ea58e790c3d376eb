"""Does a serve cell's decode program fit the chip? No chip needed.

    JAX_PLATFORMS=cpu python3 -m benchmark.aot_fit <cell> [max_seq]

Compiles the decode program of a ``serve_closed`` / ``serve_open`` cell
as the engine holds it: ``ContinuousBatchingEngine._decode_step_paged``
(the model's step, counted for an expert model, and the sampler) with
the pool donated, the weights as ``model.serving_params`` keeps them
(bf16 matmul weights), and the K/V pools and tables of an engine that
``ContinuousBatchingEngine.__init__`` itself laid out for the model's
kind (``engine_of_shapes``: built on a model whose pools are shapes, so
nothing is allocated and no layout is written down here), at the
published widths and the traffic file's engine shape, for a DESCRIBED
v5e (the TPU's compiler is installed where no TPU is). Prints the
compiler's memory analysis as one JSON line: GiB of arguments (weights +
pools), of temporaries, and their sum against the chip's 15.75 GiB. A program that does not fit
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


class ShapesOnly:
    """The model as an engine takes it, its K/V pools as shapes: an
    engine built on it sizes pools and tables (its own ``__init__``)
    and holds no array. The parameters go in as an empty tree."""

    def __init__(self, model):
        self._model = model

    def __getattr__(self, name):
        return getattr(self._model, name)

    def serving_params(self, params):
        return params

    def init_kv_pool(self, *args):
        import jax
        return jax.eval_shape(lambda: self._model.init_kv_pool(*args))

    def init_kv_pools(self, *args):
        import jax
        return jax.eval_shape(lambda: self._model.init_kv_pools(*args))


def engine_of_shapes(model, slots: int, bs: int, max_seq: int):
    """The engine ``LLMServer`` would build for this model and engine
    shape (``ray_tpu/llm/serving.py``), with nothing on a device."""
    from ray_tpu.llm.engine import ContinuousBatchingEngine

    return ContinuousBatchingEngine(ShapesOnly(model), {}, max_slots=slots,
                                    max_seq=max_seq, block_size=bs)


def table_shape(eng) -> tuple:
    """The block tables as ``_dispatch_decode`` sends them: one, or one
    a kind (a part) stacked."""
    import numpy as np

    return (eng._tables if eng.window_pool is None
            else np.stack([eng._tables, eng._tables_win])).shape


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
    params = placed(jax.eval_shape(
        lambda key: model.serving_params(model.init(key)),
        jax.random.key(0)))
    eng = engine_of_shapes(model, B, bs, max_seq)
    pool = placed(eng.kv)
    load = None if eng._ffn_counts is None else ints(*eng._ffn_counts[0].shape)
    key = jax.eval_shape(lambda: jax.random.key(0))
    compiled = jax.jit(eng._decode_step_paged, donate_argnums=(2,)).lower(
        params, ints(B), pool, ints(*table_shape(eng)), ints(B),
        jax.ShapeDtypeStruct((B,), jnp.float32, sharding=chip), ints(B),
        key, load).compile()
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
