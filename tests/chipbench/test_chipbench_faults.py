"""A whole run on the CPU with the timed path broken underneath.

Each test drives ``chipbench.run.run_cell`` (everything ``run.py`` does
after its look for a chip) on a cut-down ``mlp_ba256_linkfail`` with one
fault planted in the program, and sees ``correct`` come out false under
the cell's own limits; the clean run comes out true.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
from _tiny import run, spec

CELL = "mlp_ba256_linkfail"


def _wrap_round_fn(monkeypatch, after):
    """Make ``make_round_fn`` return a round whose result ``after`` edits."""
    from repro import fed

    orig = fed.make_round_fn

    def make(*a, **k):
        rf = orig(*a, **k)

        def broken(state, batch):
            new, metrics = rf(state, batch)
            return after(state, new), metrics

        broken.plan, broken.compression = rf.plan, rf.compression
        return broken

    monkeypatch.setattr(fed, "make_round_fn", make)


def test_clean_run_is_correct():
    result, lines = run(spec(CELL))
    assert result["correct"], lines
    assert result["failed"] == 0 and result["attempted"] == len(result["checks"])
    assert list(result)[-1] == "checks"


def test_state_left_unchanged_is_caught(monkeypatch):
    _wrap_round_fn(
        monkeypatch,
        lambda old, new: dataclasses.replace(new, params=old.params, opt_state=old.opt_state),
    )
    assert not run(spec(CELL))[0]["correct"]


def test_half_the_batch_left_out_is_caught(monkeypatch):
    from repro.fed import trainer

    orig = trainer._local_steps

    def half(loss_fn, opt, params, opt_state, batches):
        batches = jax.tree_util.tree_map(lambda b: b[:, : b.shape[1] // 2], batches)
        return orig(loss_fn, opt, params, opt_state, batches)

    monkeypatch.setattr(trainer, "_local_steps", half)
    assert not run(spec(CELL))[0]["correct"]


def test_exchange_left_out_is_caught(monkeypatch):
    from repro.core.commplan import CommPlan

    monkeypatch.setattr(CommPlan, "mix", lambda self, params, key=None, **kw: params)
    assert not run(spec(CELL))[0]["correct"]


def test_one_node_altered_is_caught(monkeypatch):
    _wrap_round_fn(
        monkeypatch,
        lambda old, new: dataclasses.replace(
            new, params=jax.tree_util.tree_map(lambda a: a.at[0].multiply(2.0), new.params)
        ),
    )
    assert not run(spec(CELL))[0]["correct"]


@pytest.mark.parametrize("cell", ["mlp_ba256_linkfail"])
def test_control_is_not_correct(cell):
    """The reference one precision step below the configuration (local steps
    in bfloat16, the mix at ``high``) in the program's place fails the
    cell's limits."""
    from chipbench import check, program, reference

    s = spec(cell)
    cfg, tr = s["cfg"], s["traffic"]
    rounds = tr["check_rounds"]
    inputs = program.make_inputs(cfg, tr, 3_000_000_002, rounds)
    ins = (cfg, tr, 3_000_000_002, inputs.adj, inputs.xs, inputs.ys, inputs.test, inputs.schedule, rounds)
    ref = reference.run(*ins)
    control = reference.run(*ins, setting=reference.Setting("bfloat16", "high"))
    correct, report, failed = check.judge(check.numbers(control, ref, rounds), s["limits"]["limits"])
    assert not correct and failed >= 1, report
    assert jnp.isfinite(jnp.asarray(ref["train_loss"])).all()
