"""Every program a cell runs compiles for a described (not attached) v5e.

The full-graph step of each configuration, and the bucket executables of
every rung and batch width the query cells warm, at the cells' shapes.
Nothing runs: this only shows what the TPU compiler would refuse.  The
topology is described inside a fixture, and the persistent compile cache
is off around these compiles (an entry written for a described chip
cannot be read back without one).

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests/test_compile_v5e.py
"""

import os

import pytest

from conftest import cell_for


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(desc.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    import jax

    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _engine(workload):
    from bench import runner

    cell = cell_for(workload)
    engine, _ds, x, _w, _dims = runner.build(
        cell, seed=1, overrides=dict(interpret=False))
    return cell, engine, x


@pytest.mark.parametrize("workload", ["pubmed-full", "citeseer-full"])
def test_full_graph_step_compiles(one_chip, workload):
    import jax

    _cell, engine, x = _engine(workload)
    params = jax.eval_shape(lambda p: p, engine.params)
    feats = jax.ShapeDtypeStruct(x.shape, x.dtype)
    text = engine._full_step.lower(
        _on(one_chip, params), _on(one_chip, feats)).compile().as_text()
    assert "tpu_custom_call" in text


def test_bucket_executables_compile(one_chip):
    """Every rung at every batch width: the closed cell's uncapped fanout
    warms the whole ladder."""
    import jax

    _cell, engine, x = _engine("pubmed-query-closed")
    batcher = engine.batcher
    for bucket in batcher.ladder.entries:
        for width in batcher.batch_ladder():
            fwd = jax.jit(batcher._make_forward(bucket, x.shape[1]))
            avals = batcher._avals(engine.params, bucket, width, x.shape[1])
            text = fwd.lower(*_on(one_chip, avals)).compile().as_text()
            assert "tpu_custom_call" in text, (bucket, width)
