"""The serving engine's program table (``serving/programs.py``): every
step mode over every cache writes each device program down once, and the
names the benchmark reads hold.

``benchmarks/families/{gpt,sdar}.py`` find the step and the prefill in a
device trace as ``jit_step_fn`` / ``jit_block_step_fn`` /
``jit_prefill_fn`` by PREFIX, and read ``engine._steps``, ``_slots``,
``_exes`` and three ``stats`` keys; no other test pins those names.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import Config
from paddle_tpu.serving import RequestParams, ServingEngine

BLOCK = dict(block_length=4, denoising_steps=2, mask_token_id=95)
MODES = {   # enable_generation's options -> (step key, its jitted name)
    "decode": ({}, ("step",), "step_fn"),
    "speculative": ({"speculative": "ngram"}, ("spec_step",),
                    "spec_step_fn"),
    "block_diffusion": ({"block_diffusion": BLOCK}, ("block_step",),
                        "block_step_fn"),
}
CACHES = {"dense": {}, "paged": dict(paged=True, kv_page_size=16)}
#: a trace's module names the benchmark matches by prefix
READ_BY_PREFIX = ("step_fn", "block_step_fn", "prefill_fn")


def _model(mode):
    paddle.seed(0)
    if mode != "block_diffusion":
        from paddle_tpu.models.gpt import gpt
        return gpt("test-tiny")
    from paddle_tpu.models.sdar import SDARConfig, SDARForCausalLM
    return SDARForCausalLM(SDARConfig(
        dtype="float32", vocab_size=96, hidden_size=32,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=8, moe_intermediate_size=16, num_experts=8,
        num_experts_per_tok=2, norm_topk_prob=True, rms_norm_eps=1e-6,
        rope_theta=1e6, max_position_embeddings=256))


def _engine(mode, **serving):
    model = _model(mode)
    model.eval()
    cfg = (Config()
           .from_layer(model, [paddle.to_tensor(np.zeros((1, 16), np.int32))])
           .enable_generation(max_new_tokens=8, prefill_buckets=(16, 32),
                              max_batch=2, eos_token_id=None,
                              **MODES[mode][0])
           .enable_serving(max_queue=8, **serving))
    return ServingEngine(cfg)


CASES = [pytest.param(m, c, id=f"{m}-{c}") for m in MODES for c in CACHES] \
    + [pytest.param("decode", "chunked", id="decode-paged-chunked")]


@pytest.mark.parametrize("mode,cache", CASES)
def test_every_program_is_written_once(mode, cache):
    serving = dict(CACHES["paged"], prefill_chunk_tokens=16) \
        if cache == "chunked" else CACHES[cache]
    eng = _engine(mode, **serving)
    try:
        table = eng._programs
        _, step_key, step_name = MODES[mode]
        want = {("prefill", 16), ("prefill", 32), step_key, ("admit",),
                ("free",), ("poll_view",)}
        if cache == "chunked":
            want |= {("chunk", 16), ("chunk_final", 16), ("install_span",)}
        # one table: what warmup() compiled and what audit() reports
        assert set(table) == want == set(eng._exes)
        reports = eng.audit()
        assert set(reports) == {p.report for p in table.values()}
        assert {"decode", "admit", "free", "poll_view"} <= set(reports)
        for rep in reports.values():
            rep.raise_on_error()
        # the cache and every lane stay in place across steps and
        # admissions, from the record's own statement of what it donates
        step, admit = table[step_key], table[("admit",)]
        assert step.donates == ("cache", "lanes", "key")
        assert set(admit.donates) >= {"cache", "lanes"}
        for prog in (step, admit):
            assert prog.donation_intent == tuple(
                list(prog.args).index(n) for n in prog.donates)
            assert reports[prog.report].donation_coverage == 1.0
        # the names a device trace shows
        assert step.jit.__name__ == step_name
        assert table[("prefill", 16)].jit.__name__ == "prefill_fn"
        assert admit.jit.__name__ == "admit_fn"   # one, for every mode
        for key, prog in table.items():
            clash = [p for p in READ_BY_PREFIX
                     if prog.jit.__name__.startswith(p)
                     and prog.jit.__name__ != p]
            assert not clash, (key, prog.jit.__name__)

        # what the benchmark's lane_progress and run summary read
        prompt = np.arange(1, 10, dtype=np.int32)
        h = eng.submit(prompt, RequestParams(max_new_tokens=6))
        eng.step()
        steps = np.asarray(eng._steps)
        assert steps.shape == (2,) and eng._slots.count(h) == 1
        assert np.asarray(eng._finished).shape == (2,)
        assert h.result(timeout=120).size == 6
        for key in ("decode_steps", "emitted_tokens", "polls"):
            assert eng.stats[key] > 0, key
        assert set(eng._exes) == want        # nothing compiled since
    finally:
        eng.shutdown()
