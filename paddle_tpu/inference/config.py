"""AnalysisConfig analog.

Reference: paddle/fluid/inference/api/analysis_config.cc + the
paddle.inference.Config python surface. Options that configured CUDA
streams, MKLDNN, or the IR pass list map to XLA equivalents or become
recorded no-ops (XLA already fuses/plans memory); the ones that matter
on TPU: model location, precision mode, and the persistent compile
cache directory (the AOT analog of the inference program cache).
"""
from __future__ import annotations

import os
from typing import Optional


class PrecisionType:
    Float32 = "float32"
    Half = "float16"
    Bfloat16 = "bfloat16"
    Int8 = "int8"


class Config:
    def __init__(self, prog_file: Optional[str] = None,
                 params_file: Optional[str] = None):
        """`prog_file` may be the path prefix produced by
        `paddle_tpu.jit.save` or `static.save_inference_model`."""
        self._model_prefix: Optional[str] = None
        self._layer = None
        self._input_spec = None
        self.precision: str = PrecisionType.Float32
        self.device: str = "tpu"
        self._memory_optim = True
        self._ir_optim = True
        self._int8_compute = False
        self._compile_cache_dir: Optional[str] = None
        self._math_threads = 1
        self._generation: Optional[dict] = None
        self._serving: Optional[dict] = None
        if prog_file is not None:
            self.set_model(prog_file, params_file)

    # ---------------------------------------------------------- model src
    def set_model(self, prefix: str, params_file: Optional[str] = None):
        """Point at a saved artifact. Accepts the path prefix used by
        jit.save (`prefix.stablehlo`) or save_inference_model
        (`prefix.pdmodel`)."""
        self._model_prefix = prefix
        return self

    def from_layer(self, layer, input_spec):
        """Serve a live Layer (re-traced under this config's precision) —
        the analog of feeding a Program straight to the predictor."""
        self._layer = layer
        self._input_spec = input_spec
        return self

    def model_dir(self) -> Optional[str]:
        return os.path.dirname(self._model_prefix) \
            if self._model_prefix else None

    # ------------------------------------------------------------- knobs
    def enable_tpu(self, precision: str = PrecisionType.Bfloat16):
        """≈ enable_use_gpu: select accelerator + serving precision."""
        self.device = "tpu"
        self.precision = precision
        return self

    def disable_gpu(self):
        self.device = "cpu"
        return self

    def enable_int8_compute(self, flag: bool = True):
        """With precision Int8, run Linear matmuls as int8 x int8 ->
        int32 on the MXU (2x bf16 peak; measured 1.5-1.8x on v5e MLP
        blocks — BASELINE.md r3) instead of weight-only dequant.
        Activations quantize with PTQ-calibrated scales when the
        served layer came from PTQ.convert(), dynamically otherwise.
        ≈ the reference PTQ deployment's int8 kernels
        (slim/quantization/post_training_quantization.py)."""
        self._int8_compute = flag
        return self

    def enable_memory_optim(self, flag: bool = True):
        self._memory_optim = flag  # XLA plans memory; recorded for parity
        return self

    def switch_ir_optim(self, flag: bool = True):
        self._ir_optim = flag  # XLA pass pipeline always runs
        return self

    def set_cpu_math_library_num_threads(self, n: int):
        self._math_threads = n
        return self

    def enable_generation(self, max_new_tokens: int = 64,
                          prefill_buckets=(64, 128, 256, 512),
                          max_batch: int = 1, do_sample: bool = False,
                          temperature: float = 1.0, top_k: int = 0,
                          top_p: float = 1.0, eos_token_id=None,
                          pad_token_id=None, speculative=None,
                          draft_model=None, kv_cache_dtype=None,
                          block_diffusion=None):
        """Generation serving mode: the predictor AOT-compiles one
        (prefill, decode) executable pair per prompt bucket at build
        time and batches ``Predictor.generate()`` requests at that
        small fixed set of right-padded prefill shapes — XLA never
        retraces under live traffic (``jit.retraces{cause=new_shape}``
        ≈ 0 at steady state). Requires a live layer implementing the
        KV-cache protocol (``Config.from_layer`` with e.g.
        ``models.gpt.GPTForCausalLM``).

        ``speculative`` enables speculative decoding on every serving
        surface built from this config (Predictor buckets and the
        ServingEngine slot scheduler): ``"ngram"`` for model-free
        prompt-lookup drafting, ``"draft"`` with ``draft_model=`` a
        small live LM sharing the vocabulary (Predictor only), or a
        ``generation.SpeculativeConfig`` to set draft-k / n-gram. The
        spec draft+verify pair is AOT-compiled per bucket next to
        prefill/decode; greedy outputs stay bitwise-equal to
        non-speculative decoding.

        ``kv_cache_dtype="int8"`` (or ``PADDLE_KV_CACHE_DTYPE``)
        quantizes the KV cache on every serving surface built from
        this config: int8 values + per-(position, head) bf16 scales,
        dequant fused inside the decode kernels — half the cache HBM
        streamed per token, double the slots/pages a fixed pool
        holds.

        ``block_diffusion`` (a dict of ``generation.BlockDiffusionConfig``
        fields: ``block_length``, ``denoising_steps``, ``remasking``,
        ``confidence_threshold``, ``mask_token_id``) serves a model that
        generates by diffusion over blocks on the ServingEngine: every
        step forwards each lane's block and unmasks its most confident
        positions or commits it. Greedy, inline prefill, no speculation;
        the layer takes ``block_length=`` through the KV-cache
        protocol."""
        from ..generation.block_diffusion import as_block_diffusion_config
        from ..generation.kv_cache import validate_cache_dtype
        from ..generation.speculative import as_spec_config
        as_block_diffusion_config(block_diffusion)  # validate eagerly
        as_spec_config(speculative, draft_model)  # validate eagerly
        validate_cache_dtype(kv_cache_dtype)      # validate eagerly too
        self._generation = dict(
            max_new_tokens=int(max_new_tokens),
            prefill_buckets=tuple(sorted(int(b) for b in prefill_buckets)),
            max_batch=int(max_batch), do_sample=bool(do_sample),
            temperature=float(temperature), top_k=int(top_k),
            top_p=float(top_p), eos_token_id=eos_token_id,
            pad_token_id=pad_token_id, speculative=speculative,
            draft_model=draft_model, kv_cache_dtype=kv_cache_dtype,
            block_diffusion=block_diffusion)
        return self

    def enable_serving(self, max_queue: int = 64, poll_every: int = 4,
                       drain_timeout_s: float = 30.0,
                       default_deadline_s=None, cache_max_len=None,
                       trace_sample=None, telemetry_port=None,
                       paged: bool = False, kv_page_size=None,
                       kv_pages=None, kv_cache_dtype=None,
                       weight_bits=None, prefill_chunk_tokens=None,
                       hbm_budget=None):
        """Continuous-batching knobs for ``paddle_tpu.serving.
        ServingEngine`` (which also needs ``enable_generation()`` — the
        engine reuses its prompt-bucket set, fixed decode batch, and
        sampling config). ``max_queue`` bounds admission (submit past
        it raises QueueFull), ``poll_every`` sets the scheduler's
        completion-poll cadence in decode steps, ``drain_timeout_s``
        bounds the graceful-shutdown drain, ``default_deadline_s``
        applies a deadline to requests that don't carry one, and
        ``cache_max_len`` overrides the shared KV ring length (default:
        largest bucket + max_new_tokens, rounded up). ``trace_sample``
        traces 1-in-N requests end to end into the flight recorder
        (default 8; 0 = off), and ``telemetry_port`` starts the
        ``core.telemetry_server`` export surface (/metrics, /healthz,
        /readyz, /flightrecorder; 0 = ephemeral port) — both also
        settable via ``PADDLE_TRACE_SAMPLE`` / ``PADDLE_TELEMETRY_PORT``.

        ``paged=True`` swaps the dense per-slot KV ring for the
        block-table PAGED cache (``generation.PagedKVCache``): K/V live
        in a pool of ``kv_pages`` fixed-size pages (default: the dense
        cache's exact HBM footprint), each slot holds an int32 page
        table, admission is gated on free PAGES as well as free slots,
        and identical prompt prefixes share pages copy-on-write —
        prefill once, reference-count many. ``kv_page_size`` (or
        ``PADDLE_KV_PAGE_SIZE``; default 128) must divide the cache
        length; outputs stay bitwise-equal to the dense cache.

        ``kv_cache_dtype="int8"`` quantizes the engine's cache (wins
        over the enable_generation value when both are set);
        ``weight_bits=4`` additionally packs the served Linear weights
        two-nibbles-per-int8 with per-channel scales (precision Int8
        weight-only only; dequant stays in-trace) — the int4 decode
        weight path.

        ``prefill_chunk_tokens`` (or ``PADDLE_PREFILL_CHUNK_TOKENS``)
        enables CHUNKED PREFILL: prompts longer than this are admitted
        that many tokens at a time, one chunk per scheduler iteration,
        interleaved with the decode dispatch — in-flight streams keep
        producing tokens while a long prompt fills its KV
        incrementally (the head-of-line TTFT fix). Must be a multiple
        of ``kv_page_size`` on paged engines; outputs stay equal to
        inline admission. Default off.

        ``hbm_budget`` (bytes, or ``"16GiB"``-style; also
        ``PADDLE_HBM_BUDGET``) declares the engine's peak-HBM budget:
        the constructor runs the static planner (``analysis.memory``)
        over the decode/admission programs and FAILS FAST when
        weights + kv pool + program peak cannot fit — an OOM caught
        before a single buffer compiles; ``health()`` then exports the
        predicted headroom for the router."""
        from ..generation.kv_cache import validate_cache_dtype
        validate_cache_dtype(kv_cache_dtype)
        if weight_bits not in (None, 4, 8):
            raise ValueError(
                f"weight_bits {weight_bits!r}: 4 (packed int4 "
                "weight-only), 8 (int8 weight-only), or None")
        self._serving = dict(
            max_queue=int(max_queue), poll_every=int(poll_every),
            drain_timeout_s=float(drain_timeout_s),
            default_deadline_s=default_deadline_s,
            cache_max_len=cache_max_len,
            trace_sample=trace_sample, telemetry_port=telemetry_port,
            paged=bool(paged), kv_page_size=kv_page_size,
            kv_pages=kv_pages, kv_cache_dtype=kv_cache_dtype,
            weight_bits=weight_bits,
            prefill_chunk_tokens=prefill_chunk_tokens,
            hbm_budget=hbm_budget)
        return self

    def set_compile_cache_dir(self, path: str):
        """Persistent XLA compile cache + serialized-executable store
        (the AOT 'optimized program' cache the reference keeps per
        AnalysisPredictor). The predictor delegates the process-global
        setup — set-once, warn-on-conflict — to the one shared
        implementation in ``paddle_tpu.jit.compile_cache``; generation
        buckets built under this config persist their compiled
        executables there and warm-load on relaunch."""
        self._compile_cache_dir = path
        return self

    # paddle.inference parity spelling; the reference's
    # exp_enable_use_gpu-era configs call this enable_*
    enable_compile_cache = set_compile_cache_dir

    def summary(self) -> str:
        return (f"Config(model={self._model_prefix or self._layer}, "
                f"device={self.device}, precision={self.precision}, "
                f"memory_optim={self._memory_optim})")
