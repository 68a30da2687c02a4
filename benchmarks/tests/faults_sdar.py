"""The two faults a block-diffusion step can have, planted underneath an
otherwise whole run of a ``sdar`` cell (``run.main(argv, patch=...)``):

* ``position_order``: a denoise step unmasks the first masked positions
  of the block instead of the most confident ones;
* ``no_commit``: a block is left the moment its last position is
  unmasked, so the cache keeps the K and V of the last denoise step,
  computed while masks were still in the block.

Each has to come out as not correct by the cell's own limits.  By hand,
on the chip as on the CPU:

    python3 benchmarks/tests/faults_sdar.py no_commit \\
        --workload sdar-l6-offline --seed 7 --seconds 20
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (os.path.dirname(BENCH), BENCH, os.path.join(BENCH, "drivers")):
    if p not in sys.path:
        sys.path.insert(0, p)


def _fresh_programs(fam):
    """A planted fault changes what a program computes, not what the
    executable store keys it by: build every program anew."""
    from paddle_tpu.jit import compile_cache
    compile_cache.set_default_store(None)   # an earlier run's, in tests
    fam.enable_compile_cache = lambda path: None


def position_order(fam):
    import jax.numpy as jnp
    from paddle_tpu.generation import block_diffusion as bd_mod
    _fresh_programs(fam)

    def first_masked(conf, cand, bd):
        rank = jnp.cumsum(cand.astype(jnp.int32), axis=1)
        return cand & (rank <= bd.unmask_per_step)

    bd_mod.select_unmask = first_masked


def no_commit(fam):
    import jax.numpy as jnp
    from paddle_tpu.generation import block_diffusion as bd_mod
    _fresh_programs(fam)

    def leave_at_once(cand_before, cand_after):
        return jnp.any(cand_before, axis=1) & ~jnp.any(cand_after, axis=1)

    bd_mod.commits_now = leave_at_once


FAULTS = {"position_order": position_order, "no_commit": no_commit}

if __name__ == "__main__":
    import run as run_mod
    fault = FAULTS[sys.argv[1]]
    rc = run_mod.main(sys.argv[2:], patch=fault)
    print(f"fault {sys.argv[1]}: correct={run_mod.main.last['correct']}",
          file=sys.stderr)
    sys.exit(rc)
