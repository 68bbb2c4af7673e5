"""Each cell's programs compile for a v5e at their real sizes, offline:
the stream cell's chunk and init at 131072 lanes, the served cell's at the
4096-lane wave, and the reference's block for each configuration.  A
compile that passes here is not a chip run."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import run

os.environ.setdefault("TPU_LOG_DIR", "disabled")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    return SingleDeviceSharding(topo.devices[0])


def _shaped(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree)


def _cell(name):
    bench = run._read(os.path.join(run.ROOT, "BENCHMARK.json"))
    return run.Cell(run.ROOT, bench, name, 1, 1, False)


@pytest.mark.parametrize("name,lanes_key", [("mm1_multi.stream", "wave"),
                                            ("mg1_sweep.served", "max_wave")])
def test_cell_programs_compile_for_v5e(one_chip, name, lanes_key):
    from cimba_tpu import config, serve
    from cimba_tpu.runner import experiment as ex
    from cimba_tpu.serve import cache as pc

    cell = _cell(name)
    cfg, sut = cell.cfg, cell.sut
    L = int(cell.mix[lanes_key])
    with config.profile(run._PROFILES[cfg["precision"]]):
        spec = sut.build(cfg)
        init_j, chunk_j = pc.get_programs(
            serve.ProgramCache(store=False), spec, mesh=None, pack=None,
            chunk_steps=int(cell.mix.get("chunk_steps", 1024)),
            with_metrics=False)
        t_end = cfg.get("horizon")
        args = (jnp.arange(L), ex._seed_column(1, L),
                None if t_end is None else ex._horizon_column(t_end, L),
                ex._slice_params(sut.params(cfg, sut.rows(cfg)[0], 100), L, 0, L))
        sims = jax.eval_shape(init_j, *args)
        chunk = chunk_j.lower(_shaped(sims, one_chip)).compile()
        init_j.lower(*_shaped(args, one_chip)).compile()
    assert chunk.memory_analysis().argument_size_in_bytes > 0


@pytest.mark.parametrize("name", ["mm1_multi.stream", "mg1_sweep.served"])
def test_reference_compiles_for_v5e(one_chip, name):
    from benchmark.refs import mg1 as ref

    cell = _cell(name)
    L = ref.BLOCK
    host = ((np.zeros(L, np.uint32),) * 2 + (np.zeros(L, np.float32),) * 3
            + (np.zeros(L, np.int32), np.zeros(L, np.float32)))
    ref._block.lower(*_shaped(tuple(jnp.asarray(a) for a in host), one_chip),
                     service=cell.sut.SERVICE, cap=int(cell.cfg["queue_cap"]),
                     dtype=jnp.dtype(jnp.float32), max_steps=1 << 16).compile()
