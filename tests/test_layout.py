"""Declarative sharding layer (docs/parallelism.md §Declarative layouts).

Tier-1 specs: the ``parallelism=`` combo-string parser (unknown axes and
over-subscribed factors fail EARLY, naming the valid axes and the live
device count), layout-table COMPLETENESS for the transformer / seq2seq /
two-tower families (a new parameter landing in silent-replicate fails),
the replicated-params audit gauge + flight line, the ACCEPTANCE pair —
fsdp x tp training of the 12L transformer matches the dp loss trajectory
from one seed, and the same checkpoint serves model-sharded through
``InferenceModel``/``DecodeEngine`` with zero unexpected recompiles —
plus the Estimator/keras ``parallelism=`` surfaces and the per-axis
collective-bytes ledger math.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from bigdl_tpu.nn.attention import Transformer
from bigdl_tpu.nn.criterion import CrossEntropyCriterion
from bigdl_tpu.optim.optim_method import SGD
from bigdl_tpu.parallel.gspmd import GSPMDTrainStep, fit_layout
from bigdl_tpu.parallel.layout import (
    SpecLayout, collective_bytes_by_axis, layout_for_model,
    register_layout, tp_activation_bytes, transformer_layout)
from bigdl_tpu.parallel.mesh_policy import (mesh_and_layout,
                                            parse_parallelism,
                                            resolve_parallelism)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB, T = 32, 8


# ---------------------------------------------------------------------------
# model zoo for the suite
# ---------------------------------------------------------------------------

def _lm12():
    """THE 12L transformer of the acceptance criteria (GPT-2-small-class
    depth at test width)."""
    return Transformer(VOCAB, hidden_size=16, num_heads=2, ffn_size=32,
                       num_layers=12, dropout=0.0, mode="lm")


def _seq2seq():
    """The translation-mode (seq2seq) Transformer — WMT config family."""
    return Transformer(VOCAB, hidden_size=16, num_heads=2, ffn_size=32,
                       num_layers=2, dropout=0.0, mode="translation")


def _two_tower():
    from bigdl_tpu.models.recsys import TwoTower

    return TwoTower(n_users=32, n_items=64, dim=8, hidden=(16,))


class _LMWrap:
    """(b, t) ids -> flat (N, V) logits, the criterion-friendly shape."""

    def __init__(self, m):
        self.m = m

    def init(self, rng, x):
        return self.m.init(rng, x)

    def forward(self, params, state, x, training=False, rng=None):
        logits, st = self.m.forward(params, state, x, training=training,
                                    rng=rng)
        return logits.reshape(-1, VOCAB), st


class _FlatCE:
    """CrossEntropy over flattened (b, t) integer targets."""

    def __init__(self):
        self.ce = CrossEntropyCriterion()

    def forward(self, out, y):
        return self.ce.forward(out, jnp.reshape(y, (-1,)))


def _param_shapes(model, *init_args):
    """Parameter SHAPES via eval_shape — no compute, no compile."""
    shapes = jax.eval_shape(lambda r, args: model.init(r, *args),
                            jax.random.PRNGKey(0), tuple(init_args))
    return shapes["params"]


# ---------------------------------------------------------------------------
# parallelism= policy strings
# ---------------------------------------------------------------------------

class TestParallelismPolicy:
    def test_parse_and_resolve(self):
        assert parse_parallelism("dp") == {"data": -1}
        assert parse_parallelism("fsdp:2,tp:4") == {"fsdp": 2, "tp": 4}
        # aliases normalize
        assert parse_parallelism("mp:2,sp:2") == {"tp": 2, "seq": 2}
        assert resolve_parallelism("dp", 8) == {
            "data": 8, "fsdp": 1, "tp": 1, "seq": 1}
        # the fill axis absorbs the remainder
        assert resolve_parallelism("tp:2,dp", 8)["data"] == 4
        assert resolve_parallelism("dp:2,fsdp:2,tp:2", 8) == {
            "data": 2, "fsdp": 2, "tp": 2, "seq": 1}

    def test_unknown_axis_lists_valid_axes(self):
        with pytest.raises(ValueError, match="unknown axis 'zz'.*fsdp"):
            parse_parallelism("dp:4,zz:2")

    def test_oversubscription_lists_live_device_count(self):
        with pytest.raises(ValueError,
                           match="needs 16 devices but only 8"):
            resolve_parallelism("dp:8,tp:2", 8)

    def test_parse_rejects_malformed(self):
        with pytest.raises(ValueError, match="given twice"):
            parse_parallelism("dp:2,data:4")
        with pytest.raises(ValueError, match="omit its factor"):
            parse_parallelism("dp,tp")
        with pytest.raises(ValueError, match="must be >= 1"):
            parse_parallelism("dp:0")
        with pytest.raises(ValueError, match="not an integer"):
            parse_parallelism("tp:two")
        with pytest.raises(ValueError, match="non-empty"):
            parse_parallelism("")

    def test_non_divisible_fill_fails(self):
        with pytest.raises(ValueError, match="not divisible"):
            resolve_parallelism("tp:3,dp", 8)

    def test_under_subscription_warns_idle_devices(self):
        # package root has propagate=False, so collect records directly
        import logging

        records = []
        lg = logging.getLogger("bigdl_tpu.parallel.mesh_policy")
        h = logging.Handler()
        h.emit = records.append
        lg.addHandler(h)
        try:
            sizes = resolve_parallelism("dp:2,tp:2", 8)
        finally:
            lg.removeHandler(h)
        assert sizes["data"] == 2  # sub-mesh stays legal (serving tp:N)
        assert any("stay idle" in r.getMessage() for r in records)

    def test_mesh_and_layout_shape(self):
        r = mesh_and_layout("fsdp:2,tp:2")
        assert dict(r.mesh.shape) == {"data": 1, "fsdp": 2, "seq": 1,
                                      "tp": 2}
        assert r.n_batch_shards == 2
        assert r.model_sharded
        assert not mesh_and_layout("dp").model_sharded

    def test_engine_config_env(self, monkeypatch):
        from bigdl_tpu.runtime.engine import EngineConfig

        monkeypatch.setenv("BIGDL_TPU_PARALLELISM", "FSDP:2,TP:4")
        assert EngineConfig.from_env().parallelism == "fsdp:2,tp:4"
        monkeypatch.delenv("BIGDL_TPU_PARALLELISM")
        assert EngineConfig.from_env().parallelism is None


# ---------------------------------------------------------------------------
# canonical specs + layout-table completeness (the satellite test: a new
# parameter landing in silent-replicate FAILS here)
# ---------------------------------------------------------------------------

class TestSpecLayout:
    def test_canonical_specs(self):
        sl = SpecLayout()
        assert sl.vocab_embedding() == P(("fsdp", "tp"), None)
        assert sl.hidden_in() == P("fsdp", "tp")
        assert sl.hidden_out() == P("tp", "fsdp")
        assert sl.col_bias() == P("tp")
        assert sl.norm() == P("fsdp")
        assert sl.batch_spec(2) == P(("data", "fsdp"), "seq")
        assert sl.batch_spec(1) == P(("data", "fsdp"))

    def test_legacy_degradation(self):
        """fsdp/seq = None collapse to the old 2-axis (data x model)
        specs, keeping the rank guard meaningful."""
        sl = SpecLayout(fsdp=None, tp="model", seq=None)
        assert sl.hidden_in() == P(None, "model")
        assert sl.hidden_out() == P("model", None)
        assert sl.vocab_embedding() == P("model", None)
        assert sl.batch_spec(1) == P("data")

    def test_tp_spec_for_path_shim_unchanged(self):
        from bigdl_tpu.parallel.gspmd import tp_spec_for_path

        assert tp_spec_for_path("attn/wq", np.zeros((4, 8))) \
            == P(None, "model")
        assert tp_spec_for_path("gate/w2", np.zeros((5,))) == P()
        assert tp_spec_for_path("embedding", np.zeros((16, 8))) \
            == P("model", None)


class TestTableCompleteness:
    @pytest.mark.parametrize("name,model,args", [
        ("lm12", _lm12, lambda: (np.zeros((1, T), np.int32),)),
        ("seq2seq", _seq2seq, lambda: (np.zeros((1, T), np.int32),
                                       np.zeros((1, T), np.int32))),
        ("two_tower", _two_tower, lambda: (np.zeros((2,), np.int32),
                                           np.zeros((2, 3), np.int32),
                                           np.zeros((2,), np.int32))),
    ])
    def test_no_silent_replication(self, name, model, args):
        m = model()
        shapes = _param_shapes(m, *args())
        table = layout_for_model(m, SpecLayout())
        audit = table.audit(shapes)
        assert audit.fallback_replicated == [], (
            f"{name}: layout table silently replicates "
            f"{audit.fallback_replicated} — add a rule or an explicit "
            "replicate-allowlist entry")
        assert len(audit.sharded) > 0

    def test_new_param_fails_the_audit(self):
        """The teeth: an unknown parameter name must land in the
        fallback list (this is what makes silent replication a test
        failure, not a perf mystery)."""
        m = _lm12()
        shapes = _param_shapes(m, np.zeros((1, T), np.int32))
        shapes["brand_new_giant_table"] = jax.ShapeDtypeStruct(
            (4096, 64), jnp.float32)
        audit = layout_for_model(m, SpecLayout()).audit(shapes)
        assert audit.fallback_replicated == ["brand_new_giant_table"]
        assert audit.fallback_elems == 4096 * 64

    def test_generic_rules_rank_pinned(self):
        """The 2-D Linear rule and the 4-D conv rule share 'weight$':
        rank pinning keeps the conv kernel's spatial dims unsharded and
        splits (cin, cout) instead."""
        from bigdl_tpu.parallel.layout import generic_layout

        table = generic_layout(SpecLayout())
        spec2, kind2 = table.spec_for("head/weight", 2)
        assert (spec2, kind2) == (P("fsdp", "tp"), "linear_kernel")
        spec4, kind4 = table.spec_for("conv1/weight", 4)
        assert (spec4, kind4) == (P(None, None, "fsdp", "tp"),
                                  "conv_kernel_cout")

    def test_register_layout_for_new_model(self):
        from bigdl_tpu.parallel.layout import (GENERIC_REPLICATE,
                                               LayoutRule, ModelLayout)

        class Exotic:
            pass

        try:
            register_layout("Exotic", lambda sl: ModelLayout(
                sl, rules=(LayoutRule("giant", r"(^|/)giant$",
                                      lambda l: l.vocab_embedding()),),
                replicate=GENERIC_REPLICATE, name="exotic"))
            table = layout_for_model(Exotic(), SpecLayout())
            assert table.name == "exotic"
            spec, kind = table.spec_for("giant", 2)
            assert spec == P(("fsdp", "tp"), None) and kind == "giant"
        finally:
            from bigdl_tpu.parallel.layout import _MODEL_TABLES

            _MODEL_TABLES.pop("Exotic", None)

    def test_audit_gauge_and_flight_line(self):
        from bigdl_tpu.obs import flight
        from bigdl_tpu.optim.metrics import global_metrics

        m = _lm12()
        shapes = _param_shapes(m, np.zeros((1, T), np.int32))
        shapes["mystery"] = jax.ShapeDtypeStruct((64, 4), jnp.float32)
        audit = layout_for_model(m, SpecLayout()).audit(shapes)
        audit.export()
        gm = global_metrics()
        assert gm.gauges["parallel.layout.replicated_params"] == 1.0
        evts = [e for e in flight.global_recorder().snapshot()
                if e["kind"] == "layout_replicated_params"]
        assert evts and evts[-1]["paths"] == ["mystery"]
        # a clean audit resets the gauge to 0
        del shapes["mystery"]
        layout_for_model(m, SpecLayout()).audit(shapes).export()
        assert gm.gauges["parallel.layout.replicated_params"] == 0.0


# ---------------------------------------------------------------------------
# ACCEPTANCE: fsdp x tp trains the 12L transformer to the dp trajectory
# from one seed, and the checkpoint serves model-sharded
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def lm12_runs():
    from bigdl_tpu.data import ArrayDataSet

    rs = np.random.RandomState(0)
    x = rs.randint(2, VOCAB, (32, T)).astype(np.int32)
    y = np.roll(x, -1, 1).astype(np.int32)
    model = _lm12()
    runs = {}
    for par in ("dp", "dp:2,fsdp:2,tp:2"):
        trained, stats = fit_layout(
            _LMWrap(model), _FlatCE(),
            SGD(learning_rate=0.05, momentum=0.9),
            ArrayDataSet(x, y), parallelism=par, batch_size=8, epochs=2,
            seed=7, log_every=0)
        runs[par] = (trained, stats)
    return model, x, runs


class TestTrajectoryParity:
    def test_fsdp_tp_matches_dp_loss_trajectory(self, lm12_runs):
        _, _, runs = lm12_runs
        dp = runs["dp"][1]["losses"]
        fsdp_tp = runs["dp:2,fsdp:2,tp:2"][1]["losses"]
        assert len(dp) == 8  # 2 epochs x 4 steps, identical data order
        np.testing.assert_allclose(fsdp_tp, dp, rtol=2e-4, atol=2e-5)
        # and it actually trained: the second epoch's mean loss drops
        # (per-batch losses are noisy under shuffling; epoch means are
        # the stable signal at 8 steps)
        assert np.mean(fsdp_tp[4:]) < np.mean(fsdp_tp[:4])

    def test_embedding_and_opt_state_sharded(self, lm12_runs):
        _, _, runs = lm12_runs
        eng = runs["dp:2,fsdp:2,tp:2"][0]._engine
        report = eng.shard_report()
        emb_shape, emb_spec = report["embedding"]
        assert emb_shape == (VOCAB, 16)
        assert emb_spec[0] == ("fsdp", "tp")
        # the SGD momentum state inherits the param sharding (no
        # replicated moments — the ZeRO/fsdp half of the layout)
        flat = jax.tree_util.tree_flatten_with_path(eng.opt_state)[0]
        wq = next(l for p, l in flat
                  if "wq" in "/".join(str(getattr(k, "key", k))
                                      for k in p))
        assert wq.shape == (16, 16)
        assert wq.addressable_shards[0].data.shape == (8, 8)

    def test_set_variables_round_trip(self, lm12_runs):
        """TrainedModel.set_variables works on a layout engine (the
        Module.loadModule analog used by forecasters): the tree is
        re-placed under the layout's NamedShardings."""
        _, x, runs = lm12_runs
        trained = runs["dp:2,fsdp:2,tp:2"][0]
        before = trained.predict(x[:4])
        v = trained._engine.get_variables()
        trained.set_variables(v)
        np.testing.assert_allclose(trained.predict(x[:4]), before,
                                   rtol=1e-6)
        with pytest.raises(ValueError, match="structure"):
            trained.set_variables({"params": {"wrong": np.zeros((2,))}})

    def test_fit_layout_rejects_indivisible_batch(self):
        from bigdl_tpu.data import ArrayDataSet

        with pytest.raises(ValueError, match="batch shards"):
            fit_layout(_LMWrap(_lm12()), _FlatCE(), SGD(0.1),
                       ArrayDataSet(np.zeros((24, T), np.int32),
                                    np.zeros((24, T), np.int32)),
                       parallelism="dp:2,fsdp:2,tp:2", batch_size=6)

    def test_fit_layout_rejects_empty_probe(self):
        from bigdl_tpu.data import ArrayDataSet

        with pytest.raises(ValueError, match="no batch"):
            fit_layout(_LMWrap(_lm12()), _FlatCE(), SGD(0.1),
                       ArrayDataSet(np.zeros((4, T), np.int32),
                                    np.zeros((4, T), np.int32)),
                       parallelism="dp:2,fsdp:2,tp:2", batch_size=8)

    def test_trained_model_predict_and_ledger(self, lm12_runs):
        _, x, runs = lm12_runs
        # TrainedModel.predict returns one output row per input row —
        # the flat (b*t, V) logits truncate to the first b rows, which
        # is plenty for the cross-layout numeric comparison
        a = runs["dp"][0].predict(x[:8])
        b = runs["dp:2,fsdp:2,tp:2"][0].predict(x[:8])
        assert a.shape == (8, VOCAB)
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4)
        stats = runs["dp:2,fsdp:2,tp:2"][1]
        by_axis = stats["collective_bytes_by_axis"]
        assert by_axis["fsdp"] > 0
        assert stats["replicated_params"] == 0
        # fsdp x tp shards the params ~4x (fsdp=2 x tp=2)
        dp_bytes = runs["dp"][1]["param_bytes_per_chip"]
        assert stats["param_bytes_per_chip"] < dp_bytes / 3


class TestServingModelSharded:
    def test_serves_sharded_with_zero_unexpected_recompiles(
            self, lm12_runs):
        from bigdl_tpu.obs.attr import recompile_sentinel
        from bigdl_tpu.optim.metrics import global_metrics
        from bigdl_tpu.serving.decode_engine import DecodeConfig
        from bigdl_tpu.serving.inference_model import InferenceModel

        model, x, runs = lm12_runs
        vars_ = runs["dp:2,fsdp:2,tp:2"][0].variables
        ref = InferenceModel(model, vars_, batch_buckets=(1, 4))
        im = InferenceModel(
            model, vars_, batch_buckets=(1, 4), layout="fsdp:2,tp:2",
            decode=DecodeConfig(slots=2, page_size=4, pages_per_slot=3,
                                prompt_chunk=4, prefill_batch=2,
                                max_new_tokens=4, eos_id=1))
        # params actually live sharded on the mesh
        emb = im._params["embedding"]
        assert emb.addressable_shards[0].data.shape == (VOCAB // 4, 16)
        sent = recompile_sentinel()
        m = global_metrics()
        try:
            im.warmup(x[0])
            ref.warmup(x[0])
            before = m.counter("train.unexpected_recompiles_total")
            sent.mark_steady()
            # mixed-size predict sweep: sharded == unsharded numerics
            rs = np.random.RandomState(3)
            for n in (1, 3, 4, 2):
                xb = x[:n]
                np.testing.assert_allclose(
                    im.predict(xb), ref.predict(xb),
                    rtol=2e-3, atol=2e-4)
            # and the decode engine generates through the sharded params
            prompts = [rs.randint(2, VOCAB, (int(k),)).astype(np.int32)
                       for k in (3, 5, 2, 7)]
            outs = im.generate(prompts, max_new_tokens=3)
            assert all(len(o) >= 1 for o in outs)
            assert all(0 <= int(t) < VOCAB for o in outs for t in o)
            after = m.counter("train.unexpected_recompiles_total")
            assert after - before == 0, (
                f"{after - before} unexpected XLA recompiles while "
                "serving the fsdp x tp checkpoint model-sharded")
        finally:
            sent.mark_warmup()
            if im.decode_engine is not None:
                im.decode_engine.stop()

    def test_layout_rejects_custom_predict_fn(self):
        from bigdl_tpu.serving.inference_model import InferenceModel

        with pytest.raises(ValueError, match="custom predict_fn"):
            InferenceModel(predict_fn=lambda x: x, layout="tp:2")


# ---------------------------------------------------------------------------
# Estimator / keras surfaces
# ---------------------------------------------------------------------------

class TestEstimatorSurface:
    def test_estimator_fit_with_parallelism(self):
        from bigdl_tpu import nn
        from bigdl_tpu.estimator import Estimator

        rs = np.random.RandomState(1)
        x = rs.randn(32, 8).astype(np.float32)
        y = rs.randn(32, 4).astype(np.float32)
        est = Estimator.from_module(
            model_creator=lambda cfg: nn.Sequential(
                [nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4)]),
            optimizer_creator=lambda cfg: SGD(learning_rate=0.05),
            loss_creator=lambda cfg: nn.MSECriterion(),
            config={"parallelism": "dp:2,tp:2", "seed": 3})
        stats = est.fit((x, y), epochs=2, batch_size=8,
                        validation_data=(x, y))
        assert stats["parallelism"] == "dp:2,tp:2"
        assert stats["mesh"] == {"data": 2, "fsdp": 1, "tp": 2, "seq": 1}
        assert stats["final_loss"] < stats["first_loss"]
        assert "Loss" in stats["validation"]
        pred = est.predict(x[:4])
        assert pred.shape == (4, 4)
        ev = est.evaluate((x, y), [])
        assert np.isfinite(list(ev.values())[0]) if ev else True

    def test_keras_fit_with_parallelism(self):
        from bigdl_tpu import nn
        from bigdl_tpu.keras.engine import Input, Model

        rs = np.random.RandomState(2)
        x = rs.randn(32, 6).astype(np.float32)
        y = rs.randn(32, 2).astype(np.float32)
        inp = Input((6,))
        out = nn.Linear(8, 2)(nn.ReLU()(nn.Linear(6, 8)(inp)))
        km = Model(inp, out).compile("sgd", "mse")
        km.fit(x, y, batch_size=8, nb_epoch=1, parallelism="tp:2",
               log_every=0)
        assert km.predict(x[:4]).shape == (4, 2)

    def test_estimator_layout_rejects_unsupported_features(self):
        from bigdl_tpu import nn
        from bigdl_tpu.estimator import Estimator

        est = Estimator.from_module(
            model_creator=lambda cfg: nn.Linear(8, 4),
            optimizer_creator=lambda cfg: SGD(learning_rate=0.1),
            loss_creator=lambda cfg: nn.MSECriterion(),
            config={"parallelism": "dp"})
        x = np.zeros((16, 8), np.float32)
        y = np.zeros((16, 4), np.float32)
        with pytest.raises(ValueError, match="fault_tolerance"):
            est.fit((x, y), epochs=1, batch_size=8, fault_tolerance=True)

    def test_keras_layout_rejects_checkpoint_path(self, tmp_path):
        from bigdl_tpu import nn
        from bigdl_tpu.keras.engine import Input, Model

        inp = Input((6,))
        km = Model(inp, nn.Linear(6, 2)(inp)).compile("sgd", "mse")
        with pytest.raises(ValueError, match="checkpoint_path"):
            km.fit(np.zeros((8, 6), np.float32),
                   np.zeros((8, 2), np.float32), parallelism="dp",
                   checkpoint_path=str(tmp_path / "ck"))

    def test_keras_parallelism_excludes_seq_parallel(self):
        from bigdl_tpu import nn
        from bigdl_tpu.keras.engine import Input, Model

        inp = Input((6,))
        km = Model(inp, nn.Linear(6, 2)(inp)).compile("sgd", "mse")
        with pytest.raises(ValueError, match="exclusive"):
            km.fit(np.zeros((8, 6), np.float32),
                   np.zeros((8, 2), np.float32),
                   parallelism="dp", seq_parallel=True)


# ---------------------------------------------------------------------------
# the per-axis ledger
# ---------------------------------------------------------------------------

class TestLedger:
    def test_per_axis_math(self):
        r = mesh_and_layout("fsdp:2,tp:2")
        params = {"w": np.zeros((4, 2), np.float32),
                  "b": np.zeros((2,), np.float32)}
        specs = {"w": P("fsdp", "tp"), "b": P()}
        led = collective_bytes_by_axis(params, specs, r.mesh)
        per = led["per_axis_bytes_per_step"]
        # w sharded on fsdp: 3 ring passes of 8*(1/2) elems * 4 B = 48
        assert per["fsdp"] == pytest.approx(3 * 8 * 0.5 * 4)
        # b replicated on the fsdp batch axis: allreduce ~2x its bytes
        assert per["data"] == pytest.approx(2 * 2 * 4)
        # per-chip params: w split 4 ways, b whole
        assert led["param_bytes_per_chip"] == pytest.approx(
            (8 / 4 + 2) * 4)

    def test_fsdp_tp_shrinks_per_chip_params_4x_at_published_width(self):
        """A count from shapes: the 12-layer d=768 transformer under
        ``"fsdp:2,tp:4"`` holds >= 4x fewer parameter bytes per chip
        than under ``"dp"``, and no parameter replicates by default."""
        model = Transformer(32768, hidden_size=768, num_heads=12,
                            ffn_size=3072, num_layers=12, dropout=0.0,
                            mode="lm")
        shapes = _param_shapes(model, np.zeros((1, 1024), np.int32))
        per_chip = {}
        for spec in ("dp", "fsdp:2,tp:4"):
            r = mesh_and_layout(spec)
            table = r.table_for(model)
            assert table.audit(shapes).fallback_replicated == []
            per_chip[spec] = collective_bytes_by_axis(
                shapes, table.param_specs(shapes),
                r.mesh)["param_bytes_per_chip"]
        assert per_chip["dp"] / per_chip["fsdp:2,tp:4"] >= 4.0

    def test_tp_activation_estimate(self):
        # 2*(tp-1)/tp * B*S*D * 4 bytes, x3 (fwd + bwd), x n collectives
        assert tp_activation_bytes(2, 4, 8, n_row_collectives=1, tp=2) \
            == pytest.approx(3 * 2 * 0.5 * 2 * 4 * 8 * 4)
        assert tp_activation_bytes(2, 4, 8, 4, tp=1) == 0.0

    def test_gspmd_legacy_ledger_counts_fsdp_as_data(self):
        from bigdl_tpu.parallel.gspmd import collective_bytes_for_specs

        r = mesh_and_layout("fsdp:4,tp:2")
        params = {"w": np.zeros((8,), np.float32)}
        rep = collective_bytes_for_specs(params, {"w": P()}, r.mesh)
        assert rep["n_data_replicas"] == 4.0
