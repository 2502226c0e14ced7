"""``correct`` comes out false for the control and for each fault the
cells can have, with the rest of a run driven as on the chip.

The runs skip the harness's look for a chip and run the tiny stand-ins of
the two cells on the CPU (Pallas in interpret mode). Their limits
(``chipbench_tiny.TINY_LIMITS``) were set as the cells' own were, from what
these sizes read on the CPU over six seeds, this test's among them: the
sound program's ``step_gap.median`` at most 1.2e-8 (LM) and ``grad_gap`` at
most 3.2e-8 (CNN), the control's at least 2.0e-7 and 5.0e-7.
"""

from __future__ import annotations

import time

import numpy as np
import pytest

from chipbench import compare, harness, refmath

SEED = 2**31 + 2**20 + 17  # larger than 32 signed bits hold
CELLS = ("tiny.lm", "tiny.cnn")


def _run(cat, cell):
    return harness.run(cell, SEED, 0.05, False, t_start=time.perf_counter(), catalog=cat,
                       on_chip=False, log=lambda _: None)


def _broken(real, fault):
    """``run_pallas`` with ``fault`` planted in the step it runs."""

    def run_pallas(program, inputs, **kw):
        import jax.numpy as jnp

        graph = program.meta["graph"]
        inputs = dict(inputs)
        x, y = np.asarray(inputs[graph.input_edge]), np.asarray(inputs[graph.label_edge])
        if fault == "half":  # the second half of the batch replaced by the first
            n = x.shape[0] // 2
            inputs[graph.input_edge] = np.concatenate([x[:n], x[:n]])
            inputs[graph.label_edge] = np.concatenate([y[:n], y[:n]])
        elif fault == "token":  # the first token (or label) altered
            edge, a = (graph.input_edge, x) if x.ndim == 2 else (graph.label_edge, y)
            a = a.copy()
            a[0] = np.roll(a[0], 1)
            inputs[edge] = a
        outs = real(program, inputs, **kw)
        if fault == "unchanged":  # the step returns its state unchanged
            outs = {k: (jnp.asarray(inputs[k[:-4]]) if k.endswith("_new") else v)
                    for k, v in outs.items()}
        return outs

    return run_pallas


@pytest.mark.parametrize("cell", CELLS)
def test_a_sound_run_is_correct(tiny_catalog, cell):
    r = _run(tiny_catalog, cell)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert list(r)[-1] == "checks" and r["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_is_not_correct(tiny_catalog, cell):
    b = harness.Bench(tiny_catalog, cell)
    items = b.family.pool(b.cfg, b.mix, SEED)
    ref, w0 = b.reference(SEED, items)
    control, _ = b.reference(SEED, items, precision="high")
    ok, checks = compare.judge(compare.numbers(b.cfg, w0, control, ref),
                               tiny_catalog.limits(cell))
    assert not ok, checks


def test_the_control_is_three_pass_forward_and_backward():
    """Each of the control's contractions, the backward ones too, is off by
    three-pass rounding (about 2**-17 relative), far above one float32
    product and far below a product of a cotangent rounded to bfloat16."""
    import jax
    import jax.numpy as jnp

    ka, kb, kg = jax.random.split(jax.random.key(0), 3)
    a = jax.random.normal(ka, (64, 256), jnp.float32)
    b = jax.random.normal(kb, (256, 128), jnp.float32)
    g = jax.random.normal(kg, (64, 128), jnp.float32)
    a64, b64, g64 = (np.asarray(x, np.float64) for x in (a, b, g))
    exact = (a64 @ b64, g64 @ b64.T, a64.T @ g64)
    y, vjp = jax.vjp(refmath.bilinear(jnp.matmul, "high"), a, b)
    for got, want in zip((y, *vjp(g)), exact):
        rel = np.linalg.norm(np.asarray(got, np.float64) - want) / np.linalg.norm(want)
        assert 1e-6 < rel < 2e-5, rel


@pytest.mark.parametrize("fault", ["unchanged", "half", "token"])
@pytest.mark.parametrize("cell", CELLS)
def test_a_broken_step_is_not_correct(tiny_catalog, cell, fault, monkeypatch):
    from repro.lower import executors

    monkeypatch.setattr(executors, "run_pallas", _broken(executors.run_pallas, fault))
    r = _run(tiny_catalog, cell)
    assert r["correct"] is False, r["checks"]
