"""Component-level invariants: MoE dispatch, Mamba2 scan, mLSTM/sLSTM,
sharding rule resolution, HLO analyzer, data pipeline."""

import dataclasses

import jax
import jax.numpy as jnp

import numpy as np
import pytest
pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.configs import get_config
from repro.models.common import Initializer, activation_fn
from repro.models.mamba2 import (
    init_mamba2,
    init_mamba_state,
    mamba2_decode_step,
    mamba2_forward,
)
from repro.models.moe import _route, expert_capacity, init_moe, moe_forward
from repro.models.xlstm import (
    init_mlstm,
    init_mlstm_state,
    init_slstm,
    init_slstm_state,
    mlstm_decode_step,
    mlstm_forward,
    slstm_decode_step,
    slstm_forward,
)


class TestMoE:
    def setup_method(self):
        self.cfg = dataclasses.replace(
            get_config("mixtral-8x22b", smoke=True), dtype="float32"
        )
        self.p = init_moe(Initializer(jax.random.PRNGKey(0), jnp.float32), self.cfg)

    def test_output_shape_and_aux(self):
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, self.cfg.d_model))
        y, aux = moe_forward(self.p, x, self.cfg)
        assert y.shape == x.shape
        assert float(aux["moe_lb_loss"]) > 0

    def test_balanced_router_lb_loss_is_one(self):
        """Uniform router -> lb_loss == E * sum(1/E * 1/E) * E = 1."""
        p = dict(self.p, router=jnp.zeros_like(self.p["router"]))
        x = jax.random.normal(jax.random.PRNGKey(2), (2, 64, self.cfg.d_model))
        _, aux = moe_forward(p, x, self.cfg)
        # with ties the top-k picks are degenerate but probs are uniform
        assert float(aux["moe_lb_loss"]) == pytest.approx(1.0, rel=0.05)

    def test_capacity_drop_changes_output(self):
        tight = dataclasses.replace(self.cfg, capacity_factor=0.25)
        x = jax.random.normal(jax.random.PRNGKey(3), (2, 64, self.cfg.d_model))
        y_full, _ = moe_forward(self.p, x, self.cfg)
        y_tight, _ = moe_forward(self.p, x, tight)
        assert float(jnp.abs(y_full - y_tight).max()) > 1e-6

    def test_expert_capacity_rounding(self):
        c = expert_capacity(self.cfg, 64)
        assert c % 8 == 0 and c >= 64 * self.cfg.top_k / self.cfg.n_experts

    def test_dropless_equals_dense_topk(self):
        """With ample capacity, MoE == explicit per-token top-k mixture."""
        cfg = dataclasses.replace(self.cfg, capacity_factor=8.0)
        x = jax.random.normal(jax.random.PRNGKey(4), (1, 8, cfg.d_model))
        y, _ = moe_forward(self.p, x, cfg)
        # dense reference
        logits = jnp.einsum("bsd,de->bse", x, self.p["router"])
        probs = jax.nn.softmax(logits, -1)
        w, idx = jax.lax.top_k(probs, cfg.top_k)
        w = w / w.sum(-1, keepdims=True)
        ref = jnp.zeros_like(x)
        for e in range(cfg.n_experts):
            h = jax.nn.silu(x @ self.p["w1"][e]) * (x @ self.p["w3"][e])
            ye = h @ self.p["w2"][e]
            mask = (idx == e).astype(x.dtype) * w
            ref += mask.sum(-1)[..., None] * ye
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=2e-4,
                                   atol=2e-4)


class TestMamba2:
    def setup_method(self):
        self.cfg = dataclasses.replace(
            get_config("zamba2-7b", smoke=True), dtype="float32"
        )
        self.p = init_mamba2(
            Initializer(jax.random.PRNGKey(0), jnp.float32), self.cfg
        )

    @pytest.mark.parametrize("S,chunk", [(8, 4), (11, 4), (16, 16), (7, 32)])
    def test_chunked_equals_stepwise(self, S, chunk):
        B = 2
        x = jax.random.normal(jax.random.PRNGKey(1), (B, S, self.cfg.d_model)) * 0.5
        y_par, st_par = mamba2_forward(self.p, x, self.cfg, chunk=chunk)
        st = init_mamba_state(self.cfg, B, jnp.float32)
        ys = []
        for t in range(S):
            yt, st = mamba2_decode_step(self.p, x[:, t], st, self.cfg)
            ys.append(yt)
        np.testing.assert_allclose(
            np.asarray(jnp.stack(ys, 1)), np.asarray(y_par), rtol=1e-4, atol=1e-4
        )
        np.testing.assert_allclose(
            np.asarray(st["h"]), np.asarray(st_par["h"]), rtol=1e-4, atol=1e-4
        )

    def test_state_continuation(self):
        """forward(x1) then forward(x2, state) == forward(concat)."""
        B, S = 1, 12
        x = jax.random.normal(jax.random.PRNGKey(2), (B, S, self.cfg.d_model)) * 0.5
        y_all, _ = mamba2_forward(self.p, x, self.cfg, chunk=4)
        y1, st = mamba2_forward(self.p, x[:, :5], self.cfg, chunk=4)
        y2, _ = mamba2_forward(self.p, x[:, 5:], self.cfg, chunk=4, state=st)
        np.testing.assert_allclose(
            np.asarray(jnp.concatenate([y1, y2], 1)), np.asarray(y_all),
            rtol=1e-4, atol=1e-4,
        )


class TestXLSTM:
    def setup_method(self):
        self.cfg = dataclasses.replace(
            get_config("xlstm-1.3b", smoke=True), dtype="float32"
        )

    @pytest.mark.parametrize("S,chunk", [(8, 4), (11, 4), (9, 16)])
    def test_mlstm_chunked_equals_stepwise(self, S, chunk):
        p = init_mlstm(Initializer(jax.random.PRNGKey(0), jnp.float32), self.cfg)
        B = 2
        x = jax.random.normal(jax.random.PRNGKey(1), (B, S, self.cfg.d_model)) * 0.5
        y_par, st_par = mlstm_forward(p, x, self.cfg, chunk=chunk)
        st = init_mlstm_state(self.cfg, B, jnp.float32)
        ys = []
        for t in range(S):
            yt, st = mlstm_decode_step(p, x[:, t], st, self.cfg)
            ys.append(yt)
        np.testing.assert_allclose(
            np.asarray(jnp.stack(ys, 1)), np.asarray(y_par), rtol=2e-4, atol=2e-4
        )
        np.testing.assert_allclose(
            np.asarray(st["C"]), np.asarray(st_par["C"]), rtol=2e-4, atol=2e-4
        )

    def test_slstm_scan_equals_stepwise(self):
        p = init_slstm(Initializer(jax.random.PRNGKey(0), jnp.float32), self.cfg)
        B, S = 2, 9
        x = jax.random.normal(jax.random.PRNGKey(1), (B, S, self.cfg.d_model)) * 0.5
        y_par, st_par = slstm_forward(p, x, self.cfg)
        st = init_slstm_state(self.cfg, B)
        ys = []
        for t in range(S):
            yt, st = slstm_decode_step(p, x[:, t], st, self.cfg)
            ys.append(yt)
        np.testing.assert_allclose(
            np.asarray(jnp.stack(ys, 1)), np.asarray(y_par), rtol=1e-5, atol=1e-5
        )

    def test_mlstm_long_range_state_stable(self):
        """No NaN/inf over a long roll-out (stabilizer works)."""
        p = init_mlstm(Initializer(jax.random.PRNGKey(0), jnp.float32), self.cfg)
        st = init_mlstm_state(self.cfg, 1, jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (1, self.cfg.d_model))
        step = jax.jit(lambda s: mlstm_decode_step(p, x, s, self.cfg))
        for _ in range(200):
            y, st = step(st)
        assert bool(jnp.isfinite(y).all())


class TestSharding:
    def test_spec_resolution_and_fallback(self):
        import jax as _jax

        from repro import sharding as sh

        mesh = _jax.make_mesh((1, 1), ("data", "model"))
        with sh.use_mesh(mesh, sh.TRAIN_RULES):
            # everything divides a 1x1 mesh
            s = sh.spec_for((8, 16), ("batch", "ffn"))
            assert len(s) == 2

    def test_divisibility_fallback_replicates(self):
        from jax.sharding import PartitionSpec as P

        from repro import sharding as sh

        # fake a bigger mesh via the abstract Mesh API
        import numpy as _np
        devs = _np.array(jax.devices() * 4).reshape(2, 2)[:1, :1]
        # single-device container: simulate with AbstractMesh
        mesh = jax.sharding.AbstractMesh((2, 2), ("data", "model"))
        ctx = sh._Ctx(mesh, sh.TRAIN_RULES)
        used = set()
        # dim 7 not divisible by model=2 -> replicated
        assert sh._resolve_dim(7, "ffn", ctx, used) is None
        # dim 8 divisible -> sharded
        assert sh._resolve_dim(8, "ffn", ctx, set()) == "model"

    def test_axis_used_once(self):
        from repro import sharding as sh

        mesh = jax.sharding.AbstractMesh((2, 2), ("data", "model"))
        ctx = sh._Ctx(mesh, sh.TRAIN_RULES)
        used = set()
        a = sh._resolve_dim(8, "ffn", ctx, used)
        b = sh._resolve_dim(8, "heads", ctx, used)  # also wants "model"
        assert a == "model" and b is None


class TestHloAnalysis:
    def test_scan_trip_count_multiplication(self):
        from repro.launch.hlo_analysis import analyze_hlo

        def f(x, w):
            def body(c, _):
                return jnp.tanh(c @ w), None
            c, _ = jax.lax.scan(body, x, None, length=7)
            return c

        xs = jax.ShapeDtypeStruct((128, 128), jnp.float32)
        txt = jax.jit(f).lower(xs, xs).compile().as_text()
        c = analyze_hlo(txt)
        assert c.flops == pytest.approx(2 * 128**3 * 7, rel=1e-6)
        assert c.unknown_trip_counts == 0

    def test_nested_scan(self):
        from repro.launch.hlo_analysis import analyze_hlo

        def f(x, w):
            def outer(c, _):
                def inner(ci, _):
                    return ci @ w, None
                ci, _ = jax.lax.scan(inner, c, None, length=3)
                return ci, None
            c, _ = jax.lax.scan(outer, x, None, length=5)
            return c

        xs = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        txt = jax.jit(f).lower(xs, xs).compile().as_text()
        c = analyze_hlo(txt)
        assert c.flops == pytest.approx(2 * 64**3 * 15, rel=1e-6)


class TestData:
    def test_deterministic_and_resumable(self):
        from repro.training.data import DataConfig, SyntheticLM

        cfg = DataConfig(vocab_size=128, seq_len=16, batch_size=4, seed=1)
        a = SyntheticLM(cfg).batch(7)
        b = SyntheticLM(cfg).batch(7)
        np.testing.assert_array_equal(a["tokens"], b["tokens"])

    def test_labels_shifted(self):
        from repro.training.data import DataConfig, SyntheticLM

        cfg = DataConfig(vocab_size=128, seq_len=16, batch_size=4)
        b = SyntheticLM(cfg).batch(0)
        np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])

    @given(seed=st.integers(0, 100))
    @settings(max_examples=10, deadline=None)
    def test_mostly_predictable(self, seed):
        """>= (1-noise-slack) of transitions follow the successor table."""
        from repro.training.data import DataConfig, SyntheticLM

        cfg = DataConfig(vocab_size=64, seq_len=64, batch_size=4, seed=seed)
        lm = SyntheticLM(cfg)
        b = lm.batch(0)
        det = lm._succ[b["tokens"]]
        frac = float(np.mean(det == b["labels"]))
        assert frac > 1 - cfg.noise - 0.1


class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        from repro.training.checkpoint import restore_checkpoint, save_checkpoint

        tree = {
            "a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.bfloat16)},
        }
        save_checkpoint(str(tmp_path), 3, tree)
        template = jax.tree.map(jnp.zeros_like, tree)
        got, step = restore_checkpoint(str(tmp_path), template)
        assert step == 3
        np.testing.assert_array_equal(np.asarray(got["a"]), np.asarray(tree["a"]))
        assert got["b"]["c"].dtype == jnp.bfloat16

    def test_latest_and_shape_check(self, tmp_path):
        from repro.training.checkpoint import (
            latest_step,
            restore_checkpoint,
            save_checkpoint,
        )

        tree = {"a": jnp.zeros((2,))}
        save_checkpoint(str(tmp_path), 1, tree)
        save_checkpoint(str(tmp_path), 5, tree)
        assert latest_step(str(tmp_path)) == 5
        bad = {"a": jnp.zeros((3,))}
        with pytest.raises(ValueError):
            restore_checkpoint(str(tmp_path), bad)


def moe_reference(p, x, cfg):
    """The Mesh-TF one-hot form of `moe_forward`: the (B, S, E, C) combine
    tensor carries the router weights, dispatch is its boolean support, and
    both move tokens by einsum. -> (out, {"moe_lb_loss"})."""
    k = cfg.top_k
    C = expert_capacity(cfg, x.shape[1])
    act = activation_fn(cfg.activation)
    gate_w, _, pos_sel, keep_k, sel, keep, probs, _ = _route(p, x, cfg, C)
    e_oh = (sel * keep).astype(x.dtype) * gate_w[..., None].astype(x.dtype)
    c_oh = jax.nn.one_hot(jnp.where(keep_k, pos_sel, C), C, dtype=x.dtype)
    combine = jnp.einsum("bske,bskc->bsec", e_oh, c_oh)
    xe = jnp.einsum("bsec,bsd->becd", (combine > 0).astype(x.dtype), x)
    h = jnp.einsum("becd,edf->becf", xe, p["w1"])
    if "w3" in p:
        h = act(h) * jnp.einsum("becd,edf->becf", xe, p["w3"])
    else:
        h = act(h)
    ye = jnp.einsum("becf,efd->becd", h, p["w2"])
    out = jnp.einsum("bsec,becd->bsd", combine, ye)
    frac_tokens = jnp.mean(sel.astype(jnp.float32).sum(2), axis=(0, 1)) / k
    frac_prob = jnp.mean(probs, axis=(0, 1))
    return out, {"moe_lb_loss": cfg.n_experts * jnp.sum(frac_tokens * frac_prob)}


class TestMoEDispatchEquivalence:
    """moe_forward (scatter/gather) == moe_reference (one-hot einsum),
    fwd and grad."""

    def _setup(self, name):
        cfg = dataclasses.replace(get_config(name, smoke=True), dtype="float32")
        p = init_moe(Initializer(jax.random.PRNGKey(0), jnp.float32), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 33, cfg.d_model))
        return cfg, p, x

    @pytest.mark.parametrize("name", ["mixtral-8x22b", "llama4-scout-17b-a16e"])
    def test_forward_equal(self, name):
        cfg, p, x = self._setup(name)
        y_e, aux_e = moe_reference(p, x, cfg)
        y_s, aux_s = moe_forward(p, x, cfg)
        np.testing.assert_allclose(
            np.asarray(y_e), np.asarray(y_s), rtol=2e-4, atol=2e-4
        )
        assert float(aux_e["moe_lb_loss"]) == pytest.approx(
            float(aux_s["moe_lb_loss"])
        )

    def test_grads_close(self):
        cfg, p, x = self._setup("mixtral-8x22b")
        gs = jax.grad(lambda q: moe_forward(q, x, cfg)[0].sum())(p)
        ge = jax.grad(lambda q: moe_reference(q, x, cfg)[0].sum())(p)
        for a, b in zip(jax.tree.leaves(gs), jax.tree.leaves(ge)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-2, atol=2e-2
            )

    def test_capacity_drops_match(self):
        """Both dispatches drop the same tokens under tight capacity."""
        cfg, p, x = self._setup("mixtral-8x22b")
        tight = dataclasses.replace(cfg, capacity_factor=0.5)
        y_e, _ = moe_reference(p, x, tight)
        y_s, _ = moe_forward(p, x, tight)
        np.testing.assert_allclose(
            np.asarray(y_e), np.asarray(y_s), rtol=2e-4, atol=2e-4
        )


class TestMicrobatching:
    def test_grads_equal_full_batch(self):
        """microbatched step == single-batch step (same update)."""
        from repro.models import RuntimeFlags, build_model
        from repro.training import AdamWConfig, adamw_init
        from repro.training.loop import make_train_step

        cfg = dataclasses.replace(
            get_config("llama2-7b", smoke=True), dtype="float32"
        )
        model = build_model(cfg, RuntimeFlags(remat=False))
        params, _ = model.init(jax.random.PRNGKey(0))
        opt = adamw_init(params)
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0,
                                  cfg.vocab_size)
        batch = {"tokens": toks, "labels": toks}
        oc = AdamWConfig()
        p1, _, m1 = make_train_step(model, oc, microbatches=1)(params, opt, batch)
        p4, _, m4 = make_train_step(model, oc, microbatches=4)(params, opt, batch)
        assert float(m1["loss"]) == pytest.approx(float(m4["loss"]), rel=1e-4)
        err = max(
            float(jnp.abs(a - b).max())
            for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p4))
        )
        assert err < 5e-5
