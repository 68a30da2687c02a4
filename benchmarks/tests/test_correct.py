"""``correct`` has to be able to fail.

At rehearsal size on the CPU (the harness's look for a chip is what the
rehearsal spec skips; the rest of a run is driven whole): a sound run is
correct; the control (the reference in fp8, put in the program's place) is
not; and with the timed path broken underneath, once for each fault a cell
can have, ``correct`` comes out false.  The limits of the rehearsal cells
(``limits/rehearsal-tiny.*.json``) were set as the real cells' are, from sound
runs and the control on the CPU; they say nothing about the chip.
"""
import os

import numpy as np
import pytest

import check
import common
import run as run_mod
import traffic

SPEC = os.path.join(common.HERE, "rehearsal.json")


def drive(workload, seed, patch=None, seconds=1.0, control=0):
    rc = run_mod.main(["--spec", SPEC, "--workload", workload, "--seed",
                       str(seed), "--seconds", str(seconds), "--trace", "0",
                       "--control", str(control)], patch=patch)
    assert rc == 0
    return run_mod.main.last


@pytest.mark.parametrize("workload", ["rehearsal-train", "rehearsal-closed",
                                      "rehearsal-open"])
def test_sound_run_is_correct(workload):
    last = drive(workload, 2_900_000_001)
    assert last["correct"], last["rows"]
    assert last["run"].attempted > 0 and last["run"].failed == 0


def _limits(name):
    return common.load_json(common.HERE, "limits", name + ".json")["limits"]


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_train_control_is_not_correct(seed):
    cfg = common.load_json(common.HERE, "configs", "rehearsal-tiny.json")
    tr = common.load_json(common.HERE, "traffic", "rehearsal-train.json")
    ref = common.load_module(
        os.path.join(common.HERE, "reference", "gpt.py"), "reference_gpt")
    batches = traffic.train_batches(tr, cfg["vocab_size"], seed)
    key = ref.seed_key(seed)
    want = check.reference_train(ref, cfg, key, batches, 3, 2)
    ctl = check.reference_train(ref, cfg, key, batches, 3, 2, quant="fp8")
    rows, ok = check.verdict(check.compare_train(ctl, want)[0],
                             _limits("rehearsal-tiny.train"))
    assert not ok, rows
    # and the reference against itself is exact
    rows, ok = check.verdict(check.compare_train(want, want)[0],
                             _limits("rehearsal-tiny.train"))
    assert ok and all(r["value"] == 0 for r in rows.values())


class _Broken:
    """A TrainStep with one fault planted around the real one."""

    def __init__(self, step, fault):
        self.step, self.fault = step, fault
        self._param_names = step._param_names
        self._params_cache = step._params_cache

    @property
    def _opt_state_tree(self):
        return self.step._opt_state_tree

    def lower(self, *a):
        return self.step.lower(*a)

    def __call__(self, x, y):
        if self.fault == "half_batch":
            n = x.shape[0] // 2
            return self.step(x[:n], y[:n])
        # "no_update": the state comes back as it went in
        import jax
        import jax.numpy as jnp
        if self.step._opt_state_tree is None:
            self.step.lower(x, y)       # seeds the optimizer's state
        params = self.step._params_cache
        keep_p = [jnp.array(p._data) for p in params]
        keep_s = jax.tree_util.tree_map(jnp.array,
                                        self.step._opt_state_tree)
        loss = self.step(x, y)
        for p, v in zip(params, keep_p):
            p._data = v
        self.step._opt_state_tree = keep_s
        return loss


@pytest.mark.parametrize("fault", ["no_update", "half_batch"])
def test_train_fault_is_not_correct(fault):
    def patch(fam):
        real = fam.build_trainer

        def broken(cfg, seq_len):
            model, opt, step = real(cfg, seq_len)
            return model, opt, _Broken(step, fault)
        fam.build_trainer = broken

    last = drive("rehearsal-train", 2_900_000_002, patch)
    assert not last["correct"], last["rows"]


def test_served_token_altered_is_not_correct():
    """One token of every answer altered where the engine produces it."""
    def patch(fam):
        real = fam.build_engine

        def broken(cfg):
            model, make = real(cfg)

            def make_broken():
                engine = make()
                complete = engine._complete

                def altered(req, toks):
                    toks = np.array(toks)
                    if toks.size:
                        toks[toks.size // 2] = \
                            (toks[toks.size // 2] + 1) % cfg["vocab_size"]
                    return complete(req, toks)
                engine._complete = altered
                return engine
            return model, make_broken
        fam.build_engine = broken

    last = drive("rehearsal-closed", 2_900_000_003, patch)
    assert not last["correct"], last["rows"]


def test_serve_control_is_not_correct():
    """The control need not decode: on a sound run's prompts and tokens,
    the token that fp8 puts first lies further below the reference's best
    than the limit allows, by the harness's own verdict."""
    last = drive("rehearsal-closed", 2_900_000_004, control=1)
    assert last["correct"], last["rows"]
    assert last["control_correct"] == {"control": False}, \
        last["run"].notes["stand_ins"]


def test_train_stand_ins_are_not_correct():
    """``--control 1`` in a training cell: the fp8 control and each planted
    fault, put in the program's place, fail the cell's limits."""
    last = drive("rehearsal-train", 2_900_000_005, control=1)
    assert last["correct"], last["rows"]
    assert last["control_correct"] == {
        "control": False, "half_batch": False, "no_update": False}, \
        last["run"].notes["stand_ins"]
