"""Checks of the benchmark's own correctness gate, on the CPU.

    python -m pytest -q benchmarks/chip/tests

Each test drives the harness at the rehearsal sizes (``run.py
--rehearse``: the served path on the CPU, kernels interpreted) or the
control at those sizes, and sees ``correct`` come out as it must:

* the bfloat16 control is not correct on any seed (at the cell's own
  widths, the session lengths a run finishes, and its sample);
* a sound run is correct;
* each fault the cells can have makes a run not correct: a table step
  that returns its state unchanged, half of the batch left out of the
  step, and a symbol altered where the frame is produced.  (Every cell
  runs on one chip: there is no exchange between chips to leave out.)
"""
from __future__ import annotations

import json
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import control  # noqa: E402
import run  # noqa: E402

CELLS = tuple(w["name"] for w in json.loads(
    (HERE.parents[1] / "BENCHMARK.json").read_text())["workloads"])


def _run(capsys, cell, seed):
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   "3", "--rehearse"])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1])


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("seed", (1, 2, 3))
def test_control_is_not_correct(cell, seed):
    # the cell's own widths, series, session lengths and sample
    assert not control.control(cell, seed, 51.0)["correct"]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(capsys, cell):
    assert _run(capsys, cell, 5)["correct"]


def _unchanged_state(step):
    import jax
    import jax.numpy as jnp

    def wrapped(table, *a, **kw):
        keep = jax.tree.map(jnp.copy, table)
        _, info = step(table, *a, **kw)
        return keep, info

    wrapped._cache_size = step._cache_size
    return wrapped


def _half_batch(step, n_valid_at):
    import jax.numpy as jnp

    def wrapped(table, *a, **kw):
        a = list(a)
        n = a[n_valid_at]
        a[n_valid_at] = jnp.where(jnp.arange(n.shape[0]) % 2 == 1, 0, n)
        return step(table, *a, **kw)

    wrapped._cache_size = step._cache_size
    return wrapped


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", ("unchanged_state", "half_batch",
                                   "altered_symbol"))
def test_fault_is_not_correct(capsys, monkeypatch, cell, fault):
    from repro.launch import stream, transport

    mode = run.load_cell(cell)["cfg"]["mode"]
    name = "_table_step_pieces" if mode == "pieces" else "_table_step"
    step = getattr(stream, name)
    if fault == "unchanged_state":
        monkeypatch.setattr(stream, name, _unchanged_state(step))
    elif fault == "half_batch":
        # n_valid is the third operand after the table in both steps
        at = 2 if name == "_table_step_pieces" else 1
        monkeypatch.setattr(stream, name, _half_batch(step, at))
    else:
        encode = transport.encode_delta

        def altered(sid, labels, endpoints):
            labels = list(labels)
            if labels:
                labels[0] = (labels[0] + 1) % 256
            return encode(sid, labels, endpoints)

        monkeypatch.setattr(transport, "encode_delta", altered)
    assert not _run(capsys, cell, 6)["correct"]
