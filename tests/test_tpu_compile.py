"""The main-path kernels compile for a TPU v5e at their real widths.

Compiled ahead of time for a described (not attached) ``v5e:2x2``
topology, one chip of it: nothing runs, so this guards what the TPU
compiler would refuse — and the compile time of the search kernel — at
no chip time.  The topology is described inside a fixture, never at
import (only one process may load the TPU library), and every compile
stays in this file and this process.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core.calibrate.asap7 import DEFAULT_CALIB
from repro.core.compiler.batched_mapper import (_jitted_search_population,
                                                _search_xs_cached,
                                                place_configs)
from repro.core.dse.device_memo import memo_init, memo_insert, memo_lookup
from repro.core.dse.encoding import GENOME_LEN, random_genomes
from repro.core.dse.engine import genomes_to_configs, prepared_workload
from repro.core.dse.ga_device import _genetics_kernel
from repro.core.dse.pareto import pareto_mask_device
from repro.core.workloads import workload_names

P = 200          # the paper's GA population
MEMO_SLOTS = 1 << 15


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A described-device compile is written to the cache but cannot be
    read back without the chip: keep the cache out of these compiles."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), jnp.asarray(x).dtype,
                                       sharding=sharding), tree)


def _compile(fn, args, sharding):
    compiled = fn.lower(*_shapes(args, sharding)).compile()
    mem = compiled.memory_analysis()
    # one chip of a v5e holds 16 GB
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9
    return compiled


def _search_args(wls, n):
    per_op, steps, n_state, tms = _search_xs_cached(
        [prepared_workload(w) for w in wls])
    tile, chip = place_configs(genomes_to_configs(
        random_genomes(np.random.default_rng(0), n)))
    return n_state, (tile, chip, per_op, steps, tms)


def test_search_kernel_all_workloads_one_body(one_chip, no_persistent_cache):
    """The exact search kernel over the whole 20-workload suite compiles
    for the chip, and holds the kernel once: its program is no larger
    than the single-workload one (W copies would each cost ~40 s of TPU
    compile time)."""
    wls = workload_names()
    n_state, args = _search_args(wls, P)
    n1, args1 = _search_args(wls[:1], P)
    fn = _jitted_search_population(DEFAULT_CALIB, n_state, True, "aggregate")
    one = _jitted_search_population(DEFAULT_CALIB, n1, True, "aggregate")
    text_all = fn.lower(*_shapes(args, one_chip)).as_text()
    text_one = one.lower(*_shapes(args1, one_chip)).as_text()
    assert len(text_all) < 1.1 * len(text_one)
    _compile(fn, args, one_chip)


def test_memo_lookup_and_insert_compile(one_chip, no_persistent_cache):
    W = len(workload_names())
    memo = memo_init(MEMO_SLOTS, W)
    canon = jnp.zeros((P, GENOME_LEN), jnp.int32)
    _compile(jax.jit(memo_lookup), (memo, canon), one_chip)
    _compile(jax.jit(memo_insert),
             (memo, canon, jnp.zeros((P, 3, W)), jnp.ones(P, bool)),
             one_chip)


def test_ga_generation_kernel_compiles(one_chip, no_persistent_cache):
    gen = _genetics_kernel(P, 5, P // 10, 0.8, 0.2)
    _compile(gen, (jnp.zeros((P, GENOME_LEN), jnp.int32), jnp.zeros(P),
                   jax.random.PRNGKey(0)), one_chip)


def test_pareto_mask_compiles(one_chip, no_persistent_cache):
    _compile(jax.jit(pareto_mask_device), (jnp.zeros((2 * P, 3)),), one_chip)
