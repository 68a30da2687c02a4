"""The faults only a hybrid of state-space, attention and shared-plus-
routed expert blocks served as one chip's share can have, planted
underneath an otherwise whole run of a ``nemotron_h`` cell
(``run.main(argv, patch=...)``):

* ``stale_state``: an admission installs the KV row and the
  convolution's window and leaves the slot's SSM state as its last
  holder left it;
* ``state_at_bucket_end``: a prefill runs at a padded bucket and hands on
  both states at the bucket's end (the padding's) instead of the ones at
  ``prompt_len``;
* ``ssm_state_bf16``: the SSM state is held in bfloat16 between steps;
* ``no_dt_bias``: ``dt = softplus(dt)`` without its bias;
* ``no_d_skip``: ``D x`` left out;
* ``norm_before_gate``: ``RMSNorm_groups(y) * silu(z)``;
* ``one_norm_group``: one norm over all ``d_inner`` channels;
* ``no_shared_expert``: the routed part alone;
* ``scaling_one``: ``routed_scaling_factor`` 1 in 2.5's place;
* ``rotary_applied``: rotary positions (``rope_theta`` of the config) on
  q and k;
* ``absent_rows_computed``: the rows routed to experts this chip does not
  hold are computed by the held ones (expert ``e`` by ``e - first`` mod
  the held count) and added.

Each has to come out as not correct by the cell's own limits, or be
named in the limits file as one the comparison cannot see.  By hand, on
the chip as on the CPU:

    python3 benchmarks/tests/faults_nemotron_h.py stale_state \\
        --workload nemotron3n-l13-offline --seed 7 --seconds 15
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (os.path.dirname(BENCH), BENCH, os.path.join(BENCH, "drivers")):
    if p not in sys.path:
        sys.path.insert(0, p)

_UNDO = []      # (object, attribute, sound value) of every planted fault


def _plant(obj, name, value):
    _UNDO.append((obj, name, getattr(obj, name)))
    setattr(obj, name, value)


def restore():
    """Put the program back (the tests plant one fault after another)."""
    while _UNDO:
        obj, name, value = _UNDO.pop()
        setattr(obj, name, value)


def _fresh_programs(fam):
    """A planted fault changes what a program computes, not what the
    executable store keys it by: build every program anew."""
    from paddle_tpu.jit import compile_cache
    compile_cache.set_default_store(None)   # an earlier run's, in tests
    fam.enable_compile_cache = lambda path: None


def _mixer():
    from paddle_tpu.models.decoder import Mamba2Mixer
    return Mamba2Mixer


def stale_state(fam):
    from paddle_tpu.generation.hybrid_cache import HybridCache
    _fresh_programs(fam)
    sound = HybridCache.install_row

    def keep_the_last_holders(self, src, slot, *where):
        out = sound(self, src, slot, *where)
        return HybridCache(out.kv, out.state[:-1] + (self.state[-1],))

    _plant(HybridCache, "install_row", keep_the_last_holders)


def state_at_bucket_end(fam):
    _fresh_programs(fam)
    sound = _mixer()._window

    def at_the_end(self, zxbcdt, w, bias, a_log, dt_bias, d_skip, norm_w,
                   prior, s0, valid):
        return sound(self, zxbcdt, w, bias, a_log, dt_bias, d_skip, norm_w,
                     prior, s0, None)

    _plant(_mixer(), "_window", at_the_end)


def ssm_state_bf16(fam):
    _fresh_programs(fam)
    sound = fam.model_config

    def bf16_state(cfg):
        conf = sound(cfg)
        conf.ssm_state_dtype = "bfloat16"
        return conf

    _plant(fam, "model_config", bf16_state)


def _both_paths(change):
    """Plant ``change(sound) -> faulty`` on the window and the step."""
    for name in ("_window", "_step"):
        _plant(_mixer(), name, change(getattr(_mixer(), name)))


def no_dt_bias(fam):
    _fresh_programs(fam)

    def change(sound):
        def faulty(self, zxbcdt, w, bias, a_log, dt_bias, *rest, **kw):
            return sound(self, zxbcdt, w, bias, a_log, dt_bias * 0, *rest,
                         **kw)
        return faulty

    _both_paths(change)


def no_d_skip(fam):
    _fresh_programs(fam)

    def change(sound):
        def faulty(self, zxbcdt, w, bias, a_log, dt_bias, d_skip, *rest,
                   **kw):
            return sound(self, zxbcdt, w, bias, a_log, dt_bias, d_skip * 0,
                         *rest, **kw)
        return faulty

    _both_paths(change)


def _finish_with(gate_first: bool, groups):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import decoder

    def finish(self, y, x, z, d_skip, norm_w, dtype):
        y = y + d_skip.astype(jnp.float32)[:, None] * x
        y = y.reshape(y.shape[:2] + (self.d_inner,))
        gate = jax.nn.silu(z.astype(jnp.float32))
        g = groups or self.groups
        if gate_first:
            return decoder._grouped_rms(y * gate, norm_w, g,
                                        self.eps).astype(dtype)
        return (decoder._grouped_rms(y, norm_w, g, self.eps)
                * gate).astype(dtype)

    return finish


def norm_before_gate(fam):
    _fresh_programs(fam)
    _plant(_mixer(), "_finish", _finish_with(False, None))


def one_norm_group(fam):
    _fresh_programs(fam)
    _plant(_mixer(), "_finish", _finish_with(True, 1))


def no_shared_expert(fam):
    from paddle_tpu.models.nemotron_h import SharedPlusRoutedExperts
    _fresh_programs(fam)
    _plant(SharedPlusRoutedExperts, "forward",
           lambda self, x: self.routed(x))


def scaling_one(fam):
    from paddle_tpu.distributed.parallel import moe
    _fresh_programs(fam)
    sound = moe.dropless_moe

    def unscaled(*args, scaling=1.0, **kw):
        return sound(*args, scaling=1.0, **kw)

    _plant(moe, "dropless_moe", unscaled)


def rotary_applied(fam):
    _fresh_programs(fam)
    sound = fam.model_config

    def with_rotary(cfg):
        conf = sound(cfg)
        conf.rope_theta = float(cfg.get("rope_theta", 10000))
        return conf

    _plant(fam, "model_config", with_rotary)


def absent_rows_computed(fam):
    from paddle_tpu.distributed.parallel import moe
    _fresh_programs(fam)
    sound = moe.dropless_moe

    def both_halves(*args, held=None, **kw):
        y, rows = sound(*args, held=held, **kw)
        if held is None:
            return y, rows
        first, count = held
        total = args[1].shape[1]            # the router's width
        for other in range(0, total, count):
            if other != first:
                y = y + sound(*args, held=(other, count), **kw)[0]
        return y, rows

    _plant(moe, "dropless_moe", both_halves)


#: what the comparison cannot see, on the chip (limits/nemotron-3-nano-
#: 30b-a3b-l13.serve.json has the readings) as at rehearsal size: the
#: state's rounding is far under what decides a greedy token
CANNOT_SEE = ("ssm_state_bf16",)

FAULTS = {f.__name__: f for f in (
    stale_state, state_at_bucket_end, ssm_state_bf16, no_dt_bias, no_d_skip,
    norm_before_gate, one_norm_group, no_shared_expert, scaling_one,
    rotary_applied, absent_rows_computed)}

if __name__ == "__main__":
    import run as run_mod
    fault = FAULTS[sys.argv[1]]
    rc = run_mod.main(sys.argv[2:], patch=fault)
    print(f"fault {sys.argv[1]}: correct={run_mod.main.last['correct']}",
          file=sys.stderr)
    sys.exit(rc)
