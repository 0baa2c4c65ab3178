"""Sharding-rule unit tests: divisibility fallback, mesh-axis dedup,
fallback chains — the logic every dry-run cell rides on."""
import pytest
from jax.sharding import PartitionSpec as P

from repro.common.sharding import DEFAULT_RULES, abstract_mesh, resolve_axis, spec_for_shape


@pytest.fixture(scope="module")
def mesh():
    # single-device CI mesh still exercises the resolution logic with
    # symbolic axis names via an abstract mesh
    return abstract_mesh((16, 16), ("data", "model"))


@pytest.fixture(scope="module")
def pod_mesh():
    return abstract_mesh((2, 16, 16), ("pod", "data", "model"))


def test_basic_resolution(mesh):
    assert spec_for_shape(("batch", None), (256, 4), mesh) == P("data", None)
    assert spec_for_shape(("embed", "mlp"), (2048, 8192), mesh) == P("data", "model")


def test_divisibility_fallback_replicates(mesh):
    # MQA: kv_heads=1 cannot shard 16-way
    assert spec_for_shape(("embed", "kv_heads", None), (2048, 1, 256), mesh) == P(
        "data", None, None
    )
    # 24 heads % 16 != 0 -> replicated
    assert spec_for_shape((None, "heads", None), (2048, 24, 128), mesh) == P(None, None, None)


def test_axis_dedup_first_claim_wins(mesh):
    # experts claims (data, model); embed then finds data used; mlp finds model used
    spec = spec_for_shape(("experts", "embed", "mlp"), (256, 7168, 2048), mesh)
    assert spec == P(("data", "model"), None, None)


def test_fallback_chain_heads_then_seq(mesh):
    # score matrices: heads dim fails (24), seq dim picks up `model`
    spec = spec_for_shape(("batch", "heads", "seq_sharded", None), (16, 24, 4096, 4096), mesh)
    assert spec == P("data", None, "model", None)
    # when heads divide, heads win and seq stays unsharded
    spec = spec_for_shape(("batch", "heads", "seq_sharded", None), (16, 32, 4096, 4096), mesh)
    assert spec == P("data", "model", None, None)


def test_partial_tuple_drop(mesh):
    # edges rule is (pod,data,model); on a pod-less mesh with an edge count
    # divisible by 16 but not 256, only `data` survives
    spec = spec_for_shape(("edges",), (16 * 3,), mesh)
    assert spec == P("data")


def test_multi_pod_batch_folds_pod(pod_mesh):
    spec = spec_for_shape(("batch", None), (256, 4), pod_mesh)
    assert spec == P(("pod", "data"), None)


def test_empty_axes_scalar(mesh):
    assert spec_for_shape((), (), mesh) == P()


def test_resolve_axis_missing_mesh_axis(mesh):
    # 'pod' absent on a single-pod mesh -> rules degrade gracefully
    assert resolve_axis("batch", mesh) == "data"
    assert resolve_axis(None, mesh) is None


def test_rules_cover_all_model_axes():
    used_by_models = {
        "batch", "embed", "vocab", "heads", "kv_heads", "mlp", "experts",
        "seq_sharded", "layers", "nodes", "edges", "table_vocab", "candidates",
        "docs", "terms", "blocks",
    }
    assert used_by_models <= set(k for k in DEFAULT_RULES if k is not None)
