"""Locality-aware lease targeting (reference: LocalityAwareLeasePolicy,
``core_worker/lease_policy.h:58``): a task whose large argument is
resident on node B leases on node B instead of pulling the bytes."""

import numpy as np
import pytest

import ray_tpu
from ray_tpu.cluster_utils import Cluster


@pytest.fixture(scope="module", autouse=True)
def cluster():
    if ray_tpu.is_initialized():    # left by an earlier file of this worker
        ray_tpu.shutdown()
    c = Cluster(head_node_args={"num_cpus": 2})
    c.add_node(num_cpus=2, resources={"b": 1.0})
    c.wait_for_nodes()
    ray_tpu.init(address=c.address)
    yield c
    ray_tpu.shutdown()
    c.shutdown()


@ray_tpu.remote(resources={"b": 0.01})
def produce_on_b(n):
    return (ray_tpu.get_runtime_context().get_node_id(),
            np.zeros(n, dtype=np.uint8))


@ray_tpu.remote
def where(pair):
    return pair[0], ray_tpu.get_runtime_context().get_node_id()


def test_large_arg_steers_lease_to_holder():
    ref = produce_on_b.remote(2 * 1024 * 1024)  # 2MB on node B
    producer_node, consumer_node = ray_tpu.get(where.remote(ref), timeout=60)
    assert consumer_node == producer_node, \
        "consumer should lease on the node holding its 2MB argument"


def test_small_arg_keeps_default_scheduling():
    """Sub-threshold args must not steer (lease reuse stays intact)."""
    ref = produce_on_b.remote(1024)  # 1KB: below LOCALITY_MIN_BYTES
    # Just needs to run correctly anywhere; no steering assertion.
    producer_node, consumer_node = ray_tpu.get(where.remote(ref), timeout=60)
    assert producer_node and consumer_node


def test_locality_yields_to_explicit_placement():
    from ray_tpu.util import NodeAffinitySchedulingStrategy

    ref = produce_on_b.remote(2 * 1024 * 1024)
    ray_tpu.get(ref, timeout=60)  # materialize on B
    head = ray_tpu.get_runtime_context().get_node_id()
    pinned = where.options(
        scheduling_strategy=NodeAffinitySchedulingStrategy(
            node_id=head, soft=False)).remote(ref)
    _, consumer_node = ray_tpu.get(pinned, timeout=60)
    assert consumer_node == head, "explicit affinity must beat locality"
