"""Serving engine + ICC scheduling: batching correctness, slot reuse,
priority admission and deadline drops."""

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import pad_kv
from repro.configs import get_config
from repro.models import RuntimeFlags, build_model
from repro.serving import (
    EngineCounters,
    GenRequest,
    ICCRequest,
    ICCServer,
    InferenceEngine,
    measure_service_time,
)
from repro.serving.engine import prefill_step, splice_slot
from repro.telemetry import EventRecorder

_CACHE = {}


def model_params(name="llama2-7b"):
    if name not in _CACHE:
        cfg = dataclasses.replace(get_config(name, smoke=True), dtype="float32")
        m = build_model(cfg, RuntimeFlags(remat=False, mamba_chunk=4,
                                          mlstm_chunk=4))
        p, _ = m.init(jax.random.PRNGKey(0))
        _CACHE[name] = (m, p)
    return _CACHE[name]


def mk_req(uid, n=10, new=5):
    m, _ = model_params()
    prompt = jax.random.randint(jax.random.PRNGKey(uid), (n,), 0,
                                m.cfg.vocab_size)
    return GenRequest(uid=uid, prompt=prompt, max_new_tokens=new)


class TestEngine:
    def test_batched_equals_sequential(self):
        m, p = model_params()
        reqs = [mk_req(i, n=8 + i, new=4) for i in range(5)]
        batched = InferenceEngine(m, p, max_batch=3, max_seq=48).generate(reqs)
        for r in reqs:
            solo = InferenceEngine(m, p, max_batch=1, max_seq=48).generate([r])
            assert solo[r.uid].tokens == batched[r.uid].tokens, r.uid

    def test_slot_reuse(self):
        m, p = model_params()
        eng = InferenceEngine(m, p, max_batch=2, max_seq=48)
        out = eng.generate([mk_req(i, new=3) for i in range(6)])
        assert len(out) == 6
        assert all(len(r.tokens) == 3 for r in out.values())

    def test_decode_s_waits_for_the_device(self, monkeypatch):
        """JAX returns before the device finishes: each decode step's time
        must cover a block_until_ready of its results."""
        m, p = model_params()
        InferenceEngine(m, p, max_batch=2, max_seq=48).generate([mk_req(0)])
        wait = 0.05
        ready = jax.block_until_ready

        def slow_ready(x):
            time.sleep(wait)
            return ready(x)

        monkeypatch.setattr(jax, "block_until_ready", slow_ready)
        eng = InferenceEngine(m, p, max_batch=2, max_seq=48)
        res = eng.generate([mk_req(1, new=4)])[1]
        assert res.decode_s >= 3 * wait  # three decode steps after prefill
        cal = measure_service_time(m, p, 10, 4, max_seq=48, repeats=1)
        assert cal["decode_s"] >= 3 * wait

    def test_keep_logits(self):
        m, p = model_params()
        eng = InferenceEngine(m, p, max_batch=2, max_seq=48)
        reqs = [mk_req(0, new=4), dataclasses.replace(mk_req(1, new=4),
                                                      keep_logits=True)]
        out = eng.generate(reqs)
        assert out[0].logits == []
        kept = out[1].logits
        assert len(kept) == 4 and kept[0].shape == (m.cfg.padded_vocab,)
        assert [int(jnp.argmax(row)) for row in kept] == out[1].tokens

    def test_reset_clears_state(self):
        m, p = model_params()
        eng = InferenceEngine(m, p, max_batch=2, max_seq=48)
        eng.generate([mk_req(0)])
        eng.reset()
        assert eng.n_active == 0 and not eng.results
        out = eng.generate([mk_req(1, new=2)])
        assert len(out[1].tokens) == 2

    def test_counters_after_a_known_generate(self):
        """max_batch 2, outputs of 3, 5 and 2 tokens: r0 and r1 step twice
        together, r2 takes r0's slot and steps once beside r1, r1 steps
        once alone: 4 steps of 2, 2, 2 and 1 active slots."""
        m, p = model_params()
        eng = InferenceEngine(m, p, max_batch=2, max_seq=48)
        reqs = [mk_req(0, new=3), mk_req(1, new=5), mk_req(2, new=2)]
        eng.generate(reqs)
        assert eng.counters == EngineCounters(
            steps=4, prefills=3, slot_steps=7,
            # a block_until_ready and one read of every slot's token per step
            step_host_syncs=4 * 2, sampled_slot_steps=0, sampled_prefills=0,
        )
        assert eng.counters.slot_steps == sum(r.max_new_tokens - 1 for r in reqs)
        eng.reset()
        assert eng.counters == EngineCounters()

    def test_mixed_batch_samples_only_where_asked(self):
        """A greedy and a sampling request share the batch: each gets its
        solo tokens, and only the sampling one takes the host path."""
        from repro.serving.engine import SamplingParams

        m, p = model_params()
        greedy = mk_req(0, new=5)
        hot = dataclasses.replace(
            mk_req(1, new=4),
            sampling=SamplingParams(temperature=1.0, top_k=20, seed=3))
        eng = InferenceEngine(m, p, max_batch=2, max_seq=48)
        out = eng.generate([greedy, hot])
        for r in (greedy, hot):
            solo = InferenceEngine(m, p, max_batch=1, max_seq=48).generate([r])
            assert solo[r.uid].tokens == out[r.uid].tokens, r.uid
        c = eng.counters
        assert c.sampled_slot_steps == hot.max_new_tokens - 1
        # of the two admissions, only the sampling one drew on the host
        assert (c.prefills, c.sampled_prefills) == (2, 1)
        # a sampled token is one more read in its step
        assert c.step_host_syncs == 2 * c.steps + c.sampled_slot_steps

    def test_decode_program_is_named_decode(self):
        """The benchmark finds the decode program in the device trace as
        `jit_decode*` (bench/benchlib/tracing.py), so its name must stay."""
        m, p = model_params()
        eng = InferenceEngine(m, p, max_batch=2, max_seq=48)
        fn = eng._decode.func
        assert fn.__name__.startswith("decode")
        tok = np.zeros((2,), np.int32)
        hlo = fn.lower(m, p, eng._cache, tok, tok).as_text()
        assert "module @jit_decode" in hlo

    def test_prefill_program_is_named_prefill(self):
        """The benchmark finds the prefill program in the device trace as
        `jit_prefill*`; the splice's program must match neither that nor
        `jit_decode*`, so that no roofline counts it."""
        m, p = model_params()
        eng = InferenceEngine(m, p, max_batch=2, max_seq=48)
        prompt = mk_req(0).prompt[None]
        hlo = prefill_step.lower(m, p, prompt).as_text()
        assert "module @jit_prefill" in hlo
        _, cache1 = jax.eval_shape(m.prefill, p, prompt)
        splice = eng._splice_slot
        hlo = splice.func.lower(eng._cache, cache1, np.int32(0),
                                **splice.keywords).as_text()
        assert "module @jit_splice_slot" in hlo

    def test_admission_compiles_once_per_prompt_length(self):
        """After a warm-up, same-length prompts into every slot compile
        neither the prefill nor the splice again: the slot is traced."""
        m, p = model_params()
        eng = InferenceEngine(m, p, max_batch=3, max_seq=48)
        eng.warmup(mk_req(0).prompt)
        sizes = (prefill_step._cache_size(), splice_slot._cache_size())
        for i in range(eng.M):
            assert eng.submit(mk_req(10 + i, new=4)) == i
        assert (prefill_step._cache_size(), splice_slot._cache_size()) \
            == sizes

    def test_recurrent_arch_engine(self):
        """Continuous batching over a state-cache arch (zamba2)."""
        m, p = model_params("zamba2-7b")
        reqs = []
        for i in range(3):
            prompt = jax.random.randint(jax.random.PRNGKey(i), (6,), 0,
                                        m.cfg.vocab_size)
            reqs.append(GenRequest(uid=i, prompt=prompt, max_new_tokens=3))
        batched = InferenceEngine(m, p, max_batch=2, max_seq=32).generate(reqs)
        for r in reqs:
            solo = InferenceEngine(m, p, max_batch=1, max_seq=32).generate([r])
            assert solo[r.uid].tokens == batched[r.uid].tokens


class TestICCServer:
    def _trace(self, n, b_total, t_comm=0.01):
        return [
            ICCRequest(mk_req(i, new=3), t_gen=0.01 * i, t_comm=t_comm,
                       b_total=b_total,
                       route="ran:cell0" if i % 2 == 0 else "mec")
            for i in range(n)
        ]

    def test_all_satisfied_when_budget_ample(self):
        m, p = model_params()
        eng = InferenceEngine(m, p, max_batch=4, max_seq=48)
        eng.warmup(mk_req(0).prompt)
        stats = ICCServer(eng, policy="priority").run(self._trace(6, 60.0))
        assert stats.n_satisfied == 6 and stats.n_dropped == 0
        # route-tagged requests break down per fleet node
        assert stats.route_total == {"ran:cell0": 3, "mec": 3}
        assert stats.route_satisfaction("ran:cell0") == 1.0
        assert stats.route_satisfaction("mec") == 1.0
        assert stats.route_satisfaction("unknown") == 0.0

    def test_infeasible_dropped_not_served(self):
        m, p = model_params()
        eng = InferenceEngine(m, p, max_batch=2, max_seq=48)
        eng.warmup(mk_req(0).prompt)
        srv = ICCServer(eng, policy="priority", est_latency=10.0)
        stats = srv.run(self._trace(4, b_total=0.001))
        assert stats.n_dropped == 4

    def _mixed_trace(self):
        """Every third request cannot meet its budget: est_latency 1 s
        drops it at admission whatever the host's speed."""
        return [
            ICCRequest(mk_req(i, new=2 + i % 3), t_gen=0.002 * i, t_comm=0.01,
                       b_total=0.001 if i % 3 == 2 else 60.0)
            for i in range(8)
        ]

    def _serve(self, trace, recorder=None):
        m, p = model_params()
        eng = InferenceEngine(m, p, max_batch=2, max_seq=48)
        eng.warmup(mk_req(0).prompt)
        srv = ICCServer(eng, policy="priority", est_latency=1.0,
                        recorder=recorder)
        return srv.run(trace), eng

    def test_recorder_stages_telescope_to_e2e(self):
        trace = self._mixed_trace()
        rec = EventRecorder(sample_every_s=1e-9)
        stats, eng = self._serve(trace, rec)
        served = [r for r in trace if r.req.uid in eng.results]
        assert len(served) == len(stats.e2e) == 6
        e2e = {}
        for r in served:
            st = rec.stage_breakdown(r.req.uid)
            assert st["radio"] == pytest.approx(r.t_comm, abs=1e-12)
            assert st["transport"] == 0.0
            assert st["queue"] >= 0.0 and st["stall"] >= -1e-12
            e2e[r.req.uid] = sum(st.values())
        np.testing.assert_allclose(sorted(e2e.values()), sorted(stats.e2e),
                                   rtol=0, atol=1e-9)
        # a resident request waited while the other slot's requests
        # prefilled
        assert max(rec.stage_breakdown(u)["stall"] for u in e2e) > 0.0
        # one sample per engine call, none throttled away
        c = eng.counters
        assert len(rec.series["engine.step"]["wall_s"]) == c.steps
        assert len(rec.series["engine.prefill"]["wall_s"]) == c.prefills == 6
        for track in ("engine.step", "engine.prefill"):
            s = rec.series[track]
            assert all(v > 0.0 for v in s["wall_s"])
            assert all(v >= 0.0 for v in s["cpu_s"])
            assert set(s) == {"t", "wall_s", "cpu_s"}

    def test_recorder_drops_carry_the_reason(self):
        rec = EventRecorder(sample_every_s=1e-9)
        stats, _ = self._serve(self._mixed_trace(), rec)
        assert stats.n_dropped == 2
        assert rec.drop_reason_counts() == {"infeasible": stats.n_dropped}

    def test_recorder_changes_no_result(self):
        plain_stats, plain = self._serve(self._mixed_trace())
        rec_stats, traced = self._serve(self._mixed_trace(), EventRecorder())
        assert {u: r.tokens for u, r in plain.results.items()} == \
            {u: r.tokens for u, r in traced.results.items()}
        for f in ("n_total", "n_satisfied", "n_dropped", "route_total",
                  "route_satisfied"):
            assert getattr(plain_stats, f) == getattr(rec_stats, f), f
        assert len(plain_stats.e2e) == len(rec_stats.e2e)
        assert plain.counters == traced.counters

    def test_priority_orders_by_slack(self):
        a = ICCRequest(mk_req(0), t_gen=0.0, t_comm=0.05, b_total=0.08)
        b = ICCRequest(mk_req(1), t_gen=0.0, t_comm=0.01, b_total=0.08)
        assert a.priority < b.priority  # less slack -> served first


class TestSampling:
    def test_greedy_default_unchanged(self):
        m, p = model_params()
        r = mk_req(42, new=4)
        a = InferenceEngine(m, p, max_batch=1, max_seq=48).generate([r])
        b = InferenceEngine(m, p, max_batch=1, max_seq=48).generate([r])
        assert a[42].tokens == b[42].tokens

    def test_stochastic_batched_equals_sequential(self):
        """Sampling keyed by (seed, uid, position): batching-invariant."""
        from repro.serving.engine import SamplingParams

        m, p = model_params()
        reqs = [
            GenRequest(
                uid=i,
                prompt=jax.random.randint(jax.random.PRNGKey(i), (8,), 0,
                                          m.cfg.vocab_size),
                max_new_tokens=4,
                sampling=SamplingParams(temperature=1.0, top_k=20, seed=7),
            )
            for i in range(3)
        ]
        batched = InferenceEngine(m, p, max_batch=3, max_seq=48).generate(reqs)
        for r in reqs:
            solo = InferenceEngine(m, p, max_batch=1, max_seq=48).generate([r])
            assert solo[r.uid].tokens == batched[r.uid].tokens

    def test_temperature_diversifies(self):
        from repro.serving.engine import SamplingParams

        m, p = model_params()
        prompt = jax.random.randint(jax.random.PRNGKey(0), (8,), 0,
                                    m.cfg.vocab_size)
        outs = set()
        for seed in range(4):
            r = GenRequest(uid=100 + seed, prompt=prompt, max_new_tokens=6,
                           sampling=SamplingParams(temperature=2.0, seed=seed))
            res = InferenceEngine(m, p, max_batch=1, max_seq=48).generate([r])
            outs.add(tuple(res[r.uid].tokens))
        assert len(outs) > 1


class TestEngineAllArchs:
    """Continuous batching works for every assigned architecture family
    (attention KV, MoE, Mamba/hybrid, xLSTM state, enc-dec cross caches)."""

    @pytest.mark.parametrize(
        "name",
        [
            "qwen1.5-110b", "mixtral-8x22b", "glm4-9b", "nemotron-4-15b",
            "zamba2-7b", "mistral-large-123b", "xlstm-1.3b",
            "llama4-scout-17b-a16e",
        ],
    )
    def test_token_archs_batched_generation(self, name):
        m, p = model_params(name)
        reqs = []
        for i in range(3):
            prompt = jax.random.randint(jax.random.PRNGKey(i), (6 + i,), 0,
                                        m.cfg.vocab_size)
            reqs.append(GenRequest(uid=i, prompt=prompt, max_new_tokens=3))
        out = InferenceEngine(m, p, max_batch=2, max_seq=32).generate(reqs)
        assert all(len(r.tokens) == 3 for r in out.values())
        solo = InferenceEngine(m, p, max_batch=1, max_seq=32).generate(
            [reqs[0]]
        )
        assert solo[0].tokens == out[0].tokens, name

    def test_encdec_engine(self):
        m, p = model_params("seamless-m4t-large-v2")
        reqs = []
        for i in range(2):
            enc = (
                jax.random.normal(jax.random.PRNGKey(i), (10, m.cfg.d_model))
                * 0.02
            )
            dec = jax.random.randint(jax.random.PRNGKey(50 + i), (4,), 0,
                                     m.cfg.vocab_size)
            reqs.append(GenRequest(
                uid=i, prompt={"enc_embeds": enc, "dec_tokens": dec},
                max_new_tokens=3,
            ))
        eng = InferenceEngine(m, p, max_batch=2, max_seq=24, enc_len=10)
        out = eng.generate(reqs)
        assert all(len(r.tokens) == 3 for r in out.values())


def _greedy_by_hand(m, p, prompt, new):
    """Greedy tokens of one request from the model alone: prefill, then
    `decode` at explicit positions, with an argmax per step."""
    if isinstance(prompt, dict):
        batch = {k: v[None] for k, v in prompt.items()}
        plen = prompt["dec_tokens"].shape[0]
    else:
        batch, plen = prompt[None], prompt.shape[0]
    logits, cache = m.prefill(p, batch)
    cache = pad_kv(cache, new)
    toks = [int(jnp.argmax(logits[0]))]
    for i in range(new - 1):
        logits, cache = m.decode(p, cache, jnp.asarray([toks[-1]], jnp.int32),
                                 jnp.asarray([plen + i], jnp.int32))
        toks.append(int(jnp.argmax(logits[0])))
    return toks


@pytest.mark.parametrize("name", ["llama2-7b", "zamba2-7b",
                                  "seamless-m4t-large-v2"])
def test_engine_greedy_equals_an_independent_loop(name):
    """Three requests over two slots: r0 and r1 step three times
    together, then r2 takes r0's slot and steps twice beside r1, at other
    positions. The engine's greedy tokens equal a loop over the model's
    own prefill and decode."""
    m, p = model_params(name)
    enc_len, reqs = 0, []
    for i, (n, new) in enumerate([(5, 4), (7, 6), (4, 3)]):
        dec = jax.random.randint(jax.random.PRNGKey(10 + i), (n,), 0,
                                 m.cfg.vocab_size)
        prompt = dec
        if m.is_encdec:
            enc_len = 8
            enc = jax.random.normal(jax.random.PRNGKey(20 + i),
                                    (enc_len, m.cfg.d_model)) * 0.02
            prompt = {"enc_embeds": enc, "dec_tokens": dec}
        reqs.append(GenRequest(uid=i, prompt=prompt, max_new_tokens=new))
    eng = InferenceEngine(m, p, max_batch=2, max_seq=24, enc_len=enc_len)
    out = eng.generate(reqs)
    assert (eng.counters.steps, eng.counters.slot_steps) == (5, 10)
    for r in reqs:
        want = _greedy_by_hand(m, p, r.prompt, r.max_new_tokens)
        assert out[r.uid].tokens == want, (name, r.uid)


def _np_splice(full, one, slot, bax):
    """numpy: `one` (batch 1 along `bax`) into `full` at `slot`, each
    shorter dim padded with -1 (int32) or 0."""
    one = np.squeeze(one, axis=bax)
    target = full.shape[:bax] + full.shape[bax + 1:]
    one = np.pad(one, [(0, w - h) for h, w in zip(one.shape, target)],
                 constant_values=-1 if one.dtype == np.int32 else 0)
    out = full.copy()
    idx = [slice(None)] * full.ndim
    idx[bax] = slot
    out[tuple(idx)] = one
    return out


@pytest.mark.parametrize("name", ["llama2-7b", "zamba2-7b", "xlstm-1.3b",
                                  "seamless-m4t-large-v2"])
def test_submit_splices_the_prefill_into_its_slot(name):
    """Three requests fill three slots, the middle one finishes, and a
    shorter prompt takes its slot. The engine's cache then equals a numpy
    splice of the model's own prefill cache into that slot, and the other
    slots are bit-identical; the first token is the prefill's argmax."""
    m, p = model_params(name)
    enc_len = 8 if m.is_encdec else 0

    def prompt(i, n):
        dec = jax.random.randint(jax.random.PRNGKey(30 + i), (n,), 0,
                                 m.cfg.vocab_size)
        if not m.is_encdec:
            return dec
        enc = jax.random.normal(jax.random.PRNGKey(40 + i),
                                (enc_len, m.cfg.d_model)) * 0.02
        return {"enc_embeds": enc, "dec_tokens": dec}

    eng = InferenceEngine(m, p, max_batch=3, max_seq=24, enc_len=enc_len)
    for i, n in enumerate([5, 9, 6]):
        eng.submit(GenRequest(uid=i, prompt=prompt(i, n), max_new_tokens=4))
    eng._finish(1)
    before = jax.tree.map(np.array, eng._cache)
    new = prompt(3, 4)
    assert eng.submit(GenRequest(uid=3, prompt=new, max_new_tokens=4)) == 1

    batch = jax.tree.map(lambda v: v[None], new)
    logits, cache1 = jax.jit(m.prefill)(p, batch)
    assert eng.results[3].tokens == [int(np.argmax(np.asarray(logits[0])))]
    _, caxes = m.init_cache(3, 24, enc_len=enc_len)
    want = jax.tree.map(
        lambda full, one, ax: _np_splice(full, np.asarray(one), 1,
                                         ax.index("kv_batch")),
        before, cache1, caxes)
    got = jax.tree.map(np.asarray, eng._cache)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(g, w)


def test_launch_serve_writes_a_chrome_trace(tmp_path):
    """`launch/serve.py --trace PATH`: one Chrome trace per policy, with a
    span group per served request and the engine's per-call tracks."""
    import json

    from repro.launch.serve import serve

    cfg = dataclasses.replace(get_config("llama2-7b", smoke=True),
                              dtype="float32")
    rep = serve(cfg, policies=("priority",), rate=20.0, duration=0.2,
                n_input=6, n_output=3, budget=60.0, max_batch=2,
                trace_path=str(tmp_path / "out.json"))
    with open(tmp_path / "out.priority.json") as f:
        tr = json.load(f)
    served = rep.runs[0].results
    assert served
    names = {e.get("name") for e in tr["traceEvents"]}
    assert "engine.step" in names and "engine.prefill" in names
    jobs = {int(e["id"]) for e in tr["traceEvents"] if e.get("cat") == "job"}
    assert jobs == set(served)
