"""EngineConfig (ISSUE 10 satellite): the one frozen knob namespace.

Gates the api_redesign contract: field-space validation at construction,
index-dependent validation in ``validate``, the ``from_flags`` CLI
mapping every entry point shares, and the legacy constructor shim —
both the (params, index) argument order and the keyword-knob spelling —
warning ``DeprecationWarning`` while building engines whose responses
are bit-identical to the config-first spelling.
"""
import argparse
import dataclasses

import numpy as np
import pytest
import jax

from repro.core import SAEConfig, build_index, encode, init_params
from repro.core.segments import SegmentedIndex
from repro.errors import EngineConfigError
from repro.serving import (
    EngineConfig,
    RetrievalEngine,
    RetrievalResponse,
    ServingStatus,
)

CFG = SAEConfig(d=32, h=128, k=8)


@pytest.fixture(scope="module")
def setup():
    params = init_params(CFG, jax.random.PRNGKey(0))
    corpus = jax.random.normal(jax.random.PRNGKey(1), (310, CFG.d))
    queries = jax.random.normal(jax.random.PRNGKey(2), (9, CFG.d))
    codes = encode(params, corpus, CFG.k)
    index = build_index(codes, params)
    qindex = build_index(codes, params, quantize=True)
    return params, index, qindex, queries


def _bit_equal(a: RetrievalResponse, b: RetrievalResponse):
    np.testing.assert_array_equal(np.asarray(a.scores), np.asarray(b.scores))
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))


# ------------------------------------------------------ the frozen value
def test_config_is_frozen_and_replace_copies():
    cfg = EngineConfig(precision="exact")
    with pytest.raises(dataclasses.FrozenInstanceError):
        cfg.precision = "int8"
    cfg2 = cfg.replace(precision="int8", stage="single")
    assert cfg2.precision == "int8" and cfg.precision == "exact"
    assert cfg2 is not cfg


@pytest.mark.parametrize("bad", [
    dict(mode="dense"),
    dict(stage="three_stage"),
    dict(stage1="gpu"),
    dict(precision="fp64"),
    dict(stage="two_stage", mode="reconstructed"),
    dict(stage="two_stage", candidate_fraction=0.0),
    dict(stage="two_stage", candidate_fraction=1.5),
    dict(stage="two_stage", mesh=object()),
])
def test_field_space_validation_rejects_at_construction(bad):
    """Invalid combinations die the moment the config exists — before
    any index or params are in sight."""
    with pytest.raises(EngineConfigError):
        EngineConfig(**bad)


def test_index_dependent_validation(setup):
    params, index, qindex, _ = setup
    EngineConfig(precision="int8").validate(qindex)          # ok
    with pytest.raises(EngineConfigError, match="QuantizedIndex"):
        EngineConfig(precision="int8").validate(index)       # fp32 codes
    with pytest.raises(EngineConfigError, match="requires SAE params"):
        EngineConfig(mode="reconstructed").validate(index, params=None)
    wrong = {**params, "w_enc": params["w_enc"][:, : CFG.h // 2]}
    with pytest.raises(EngineConfigError, match="latent-dim mismatch"):
        EngineConfig().validate(index, wrong)
    seg = SegmentedIndex.from_index(index)
    with pytest.raises(EngineConfigError, match="single"):
        EngineConfig(stage="two_stage").validate(seg)
    with pytest.raises(EngineConfigError, match="sparse"):
        EngineConfig(mode="reconstructed").validate(seg, params)


# ---------------------------------------------------------- CLI plumbing
def _flags(argv):
    ap = argparse.ArgumentParser()
    EngineConfig.add_flags(ap)
    return ap.parse_args(argv)


def test_from_flags_default_namespace_is_default_config():
    assert EngineConfig.from_flags(_flags([])) == EngineConfig()


def test_from_flags_maps_every_knob():
    cfg = EngineConfig.from_flags(_flags([
        "--use-kernel", "0", "--quantized", "--precision", "int8",
        "--two-stage", "--candidate-fraction", "0.5",
        "--inverted-cap", "512", "--stage1", "host",
    ]))
    assert cfg.use_kernel is False and cfg.precision == "int8"
    assert cfg.stage == "two_stage" and cfg.stage1 == "host"
    assert cfg.candidate_fraction == 0.5 and cfg.inverted_cap == 512
    assert cfg.mesh is None
    assert EngineConfig.from_flags(
        _flags(["--use-kernel", "1"])).use_kernel is True


def test_from_flags_cross_checks():
    """The checks that used to be duplicated per entry point as
    ``ap.error(...)`` now live in ONE place and raise typed."""
    with pytest.raises(EngineConfigError, match="requires --quantized"):
        EngineConfig.from_flags(_flags(["--precision", "int8"]))
    with pytest.raises(EngineConfigError, match="--shards"):
        EngineConfig.from_flags(_flags(["--two-stage", "--shards", "2"]))
    with pytest.raises(EngineConfigError, match="requires --two-stage"):
        EngineConfig.from_flags(_flags(["--stage1", "device"]))


def test_from_flags_builds_shard_mesh():
    n = min(2, jax.device_count())
    if n < 2:
        pytest.skip("single-device process")
    cfg = EngineConfig.from_flags(_flags(["--shards", str(n)]))
    assert cfg.mesh is not None and "cand" in cfg.mesh.axis_names


# ------------------------------------------------------ the legacy shim
def test_legacy_argument_order_warns_and_is_equivalent(setup):
    params, index, _, queries = setup
    new = RetrievalEngine(index, params)
    with pytest.warns(DeprecationWarning, match="argument order"):
        old = RetrievalEngine(params, index)
    assert old.index is new.index and old.params is new.params
    assert old.config == new.config
    _bit_equal(old.retrieve_dense(queries, 7),
               new.retrieve_dense(queries, 7))


def test_legacy_paramless_order_warns_and_is_equivalent(setup):
    _, index, _, _ = setup
    new = RetrievalEngine(index, None)
    with pytest.warns(DeprecationWarning, match="argument order"):
        old = RetrievalEngine(None, index)
    assert old.index is new.index and old.params is None


def test_legacy_keyword_knobs_warn_and_match_config(setup):
    params, _, qindex, queries = setup
    new = RetrievalEngine(qindex, params, config=EngineConfig(
        use_kernel=False, precision="int8", k=4))
    with pytest.warns(DeprecationWarning, match="config=EngineConfig"):
        old = RetrievalEngine(qindex, params,
                              use_kernel=False, precision="int8", k=4)
    assert old.config == new.config
    _bit_equal(old.retrieve_dense(queries, 7),
               new.retrieve_dense(queries, 7))


def test_legacy_both_orders_and_knobs_together(setup):
    """The fully-legacy spelling — old order AND keyword knobs — still
    lands on the same engine as the config-first spelling."""
    params, index, _, queries = setup
    new = RetrievalEngine(index, params,
                          config=EngineConfig(use_kernel=False))
    with pytest.warns(DeprecationWarning):
        old = RetrievalEngine(params, index, use_kernel=False)
    assert old.config == new.config
    _bit_equal(old.retrieve_dense(queries, 5),
               new.retrieve_dense(queries, 5))


def test_config_and_legacy_knobs_conflict(setup):
    params, index, _, _ = setup
    with pytest.raises(EngineConfigError, match="not both"):
        RetrievalEngine(index, params, config=EngineConfig(),
                        use_kernel=False)


def test_unknown_keyword_is_a_type_error(setup):
    params, index, _, _ = setup
    with pytest.raises(TypeError, match="unexpected keyword"):
        RetrievalEngine(index, params, use_kernle=False)


def test_unidentifiable_arguments_raise_typed(setup):
    with pytest.raises(EngineConfigError, match="could not identify"):
        RetrievalEngine({"not": "params"}, 42)


# ----------------------------------------------------- response surface
def test_response_surface_is_unified(setup):
    params, index, _, queries = setup
    engine = RetrievalEngine(index, params,
                             config=EngineConfig(use_kernel=False))
    resp = engine.retrieve_dense(queries, 7)
    assert isinstance(resp, RetrievalResponse)
    assert isinstance(resp.status, ServingStatus)
    assert resp.status.path and not resp.status.degraded
    assert resp.queue_us == 0.0
    assert resp._fields == ("scores", "ids", "status", "queue_us")
    # the tuple-era contract survives: positional access + .pair
    scores, ids, *_ = resp
    assert scores is resp.scores and ids is resp.ids
    assert resp.pair == (resp.scores, resp.ids)
    assert resp[0] is resp.scores and resp[1] is resp.ids
