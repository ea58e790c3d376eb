"""Scheduling policy tests (reference: raylet/scheduling policy suite)."""

import time
from collections import Counter as Histogram

import pytest

import ray_tpu
from ray_tpu import exceptions as exc
from ray_tpu._private.scheduler import SchedulingError


@ray_tpu.remote
def where():
    return ray_tpu.get_runtime_context().get_node_id()


def test_spread_strategy(ray_start_cluster):
    refs = [where.options(scheduling_strategy="SPREAD").remote()
            for _ in range(16)]
    hist = Histogram(ray_tpu.get(refs))
    assert len(hist) == 4  # all 4 nodes used
    assert max(hist.values()) <= 6  # roughly even


def test_node_affinity_hard(ray_start_cluster):
    rt = ray_start_cluster
    target = rt.nodes()[2]
    strat = ray_tpu.NodeAffinitySchedulingStrategy(
        node_id=target.node_id.hex(), soft=False)
    got = ray_tpu.get(where.options(scheduling_strategy=strat).remote())
    assert got == target.node_id.hex()


def test_node_affinity_dead_node_fails(ray_start_cluster):
    rt = ray_start_cluster
    victim = rt.nodes()[3]
    rt.remove_node(victim)
    strat = ray_tpu.NodeAffinitySchedulingStrategy(
        node_id=victim.node_id.hex(), soft=False)
    with pytest.raises(SchedulingError):
        ray_tpu.get(where.options(scheduling_strategy=strat).remote(),
                    timeout=10)


def test_node_affinity_soft_falls_back(ray_start_cluster):
    rt = ray_start_cluster
    victim = rt.nodes()[3]
    victim_hex = victim.node_id.hex()
    rt.remove_node(victim)
    strat = ray_tpu.NodeAffinitySchedulingStrategy(node_id=victim_hex,
                                                   soft=True)
    got = ray_tpu.get(where.options(scheduling_strategy=strat).remote(),
                      timeout=10)
    assert got != victim_hex


def test_custom_resources():
    rt = ray_tpu.init(num_nodes=2, resources={"CPU": 4})
    special = rt.add_node({"CPU": 2, "special": 1.0})

    @ray_tpu.remote(resources={"special": 1})
    def on_special():
        return ray_tpu.get_runtime_context().get_node_id()

    assert ray_tpu.get(on_special.remote()) == special.node_id.hex()


def test_infeasible_task_errors(ray_start_regular):
    @ray_tpu.remote(num_cpus=1000)
    def huge():
        return 1

    with pytest.raises(Exception):
        ray_tpu.get(huge.remote(), timeout=10)


def test_label_scheduling():
    rt = ray_tpu.init(num_nodes=1, resources={"CPU": 4})
    gpuish = rt.add_node({"CPU": 4}, labels={"tier": "accel"})
    strat = ray_tpu.NodeLabelSchedulingStrategy(hard={"tier": "accel"})
    got = ray_tpu.get(where.options(scheduling_strategy=strat).remote())
    assert got == gpuish.node_id.hex()


def test_resource_queueing(ray_start_regular):
    # 8 CPUs; 4 tasks of 4 CPUs must run in two waves.
    @ray_tpu.remote(num_cpus=4)
    def hold():
        time.sleep(0.3)
        return time.monotonic()

    t0 = time.monotonic()
    times = ray_tpu.get([hold.remote() for _ in range(4)])
    assert max(times) - t0 >= 0.55  # two waves of 0.3s


def test_fractional_resources(ray_start_regular):
    @ray_tpu.remote(num_cpus=0.5)
    def half():
        return 1

    assert sum(ray_tpu.get([half.remote() for _ in range(16)])) == 16


def test_locality_preference(ray_start_cluster):
    rt = ray_start_cluster

    @ray_tpu.remote
    def produce():
        import numpy as np
        return np.ones((800, 800))  # big enough for node store

    @ray_tpu.remote
    def consume(x):
        return ray_tpu.get_runtime_context().get_node_id()

    data = produce.remote()
    ray_tpu.get(data)
    holder = None
    for node in rt.nodes():
        if node.store.contains(data.id):
            holder = node
            break
    assert holder is not None

    def wait_idle():
        # get() returns when the result is stored; the task's CPU goes
        # back to the ledger later, in the node's next dispatch pass.
        # A CPU still held there outweighs the locality bias (0.25 of
        # utilization against 0.1), so submit only to an idle cluster.
        deadline = time.monotonic() + 30
        while any(n.effective_available() != n.ledger.total
                  for n in rt.nodes()):
            assert time.monotonic() < deadline, "cluster never went idle"
            time.sleep(0.005)

    # Sequential submissions (idle cluster each time): locality bias wins.
    consumer_nodes = []
    for _ in range(6):
        wait_idle()
        consumer_nodes.append(ray_tpu.get(consume.remote(data)))
    assert Histogram(consumer_nodes)[holder.node_id.hex()] >= 5
