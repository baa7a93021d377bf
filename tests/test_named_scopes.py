"""Named scopes of the training step.

The five ``jax.named_scope``s of ``make_sde_train_step`` under
``make_scanned_step`` (``docs/performance.md``) are HLO metadata only: they
name the compiled step's instructions in ``op_name`` for a profiler to group
by.  Invariants under test, on a small Neural Langevin SDE compiled on the
CPU, under the reversible and the full adjoint, with a moment loss and a
signature loss:

* each of the five scopes names some instruction of the compiled step;
* every fusion, dot and reduce-window of the scanned while body carries one
  of them, but for what no scope in the program can name: instructions that
  XLA makes without metadata, the scanned loop's own counter and history
  writes, the loop-index constants that differentiation hoists out of a
  scan (``iota``), and the vmap boundary of ``sdeint`` (the path axis moved
  to the front of the results and back, and the sum over paths of the
  per-path parameter cotangents);
* a reversible batch solved as one (``reversible-paths``: the vmap inside
  the custom vjp, as ``sdeint`` does where the per-path cotangent carry
  would be large) has no vmap boundary, so no exception for it;
* the full adjoint has no ``sde_reverse``: its backward is the transpose of
  ``sde_forward`` and counts as the forward.

An op belongs to the last scope in its path, as ``bench/trace_reduce.py``
reads it: the transpose of the loss counts as the loss.
"""
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.nsde import (init_lsde, lsde_readout, lsde_term, moment_mse,
                        signature_mmd)
from repro.optim import adamw, cosine_schedule
from repro.train.trainer import (init_scan_counters, make_scanned_step,
                                 make_sde_train_step)

SCOPES = ("sde_brownian", "sde_forward", "sde_reverse", "sde_loss",
          "sde_optimizer")
_SCOPE = re.compile(r"(?:^|[/(])(" + "|".join(SCOPES) + r")(?=[/)]|$)")
_BODY = "jit(scanned)/while/body"
# op_names that no scope in the program can carry (see the module docstring)
_UNNAMEABLE = {
    "", f"{_BODY}/closed_call", f"{_BODY}/add",
    f"{_BODY}/dynamic_update_slice", f"{_BODY}/closed_call/iota",
}
_VMAP_BOUNDARY = {
    f"{_BODY}/closed_call/jvp(vmap())/mul",
    f"{_BODY}/closed_call/jvp(vmap())/transpose",
    f"{_BODY}/closed_call/transpose(jvp(vmap()))/transpose",
    f"{_BODY}/closed_call/transpose(jvp(vmap()))/reduce_sum",
}
_RANKED = ("fusion", "dot", "reduce-window")


def _scope_of(path):
    found = _SCOPE.findall(path)
    return found[-1] if found else None


def _compiled_hlo(route, loss):
    """``route`` is an adjoint, or ``reversible-paths``: the reversible
    batch solved as one, whatever its size."""
    adjoint = route.split("-")[0]
    with pytest.MonkeyPatch.context() as mp:
        if route == "reversible-paths":
            mp.setattr(sys.modules["repro.core.sdeint"],
                       "_PER_PATH_CARRY_BYTES", 0)
        return _compiled_step_hlo(adjoint, loss)


def _compiled_step_hlo(adjoint, loss):
    target = jax.random.normal(jax.random.PRNGKey(1), (16, 3))

    def loss_of(p, r):
        gen = lsde_readout(p, r.ys)[..., 0]
        return (moment_mse(gen, target) if loss == "moment_mse"
                else signature_mmd(1.0 + 0.1 * gen, target))

    params = init_lsde(jax.random.PRNGKey(0), d_obs=1, d_z=4, width=8)
    opt = adamw(cosine_schedule(1e-2, 2, 16), max_grad_norm=1.0)
    step = make_sde_train_step(
        "ees25", lsde_term(), opt,
        y0_fn=lambda p: jnp.zeros_like(p["encoder"]["b"]) + p["encoder"]["b"],
        loss_fn_result=loss_of, t0=0.0, t1=1.0, n_steps=6, n_paths=8,
        adjoint=adjoint, save_every=2)
    scanned = make_scanned_step(step, 2)
    return scanned.lower(params, opt.init(params), init_scan_counters(),
                         jax.random.PRNGKey(2), np.int32(0)).compile().as_text()


def _instructions(hlo):
    """(computation, opcode, op_name, line) of every instruction."""
    out, comp = [], None
    for line in hlo.split("\n"):
        head = re.match(r"^(?:ENTRY )?%(\S+) .*\{$", line)
        if head:
            comp = head.group(1)
            continue
        ins = re.match(r"^\s+(?:ROOT )?%\S+ = .*?\s([a-z][a-z-]*)\(", line)
        if ins and comp is not None:
            path = re.search(r'op_name="([^"]*)"', line)
            out.append((comp, ins.group(1), path.group(1) if path else "",
                        line))
    return out


def _reachable(hlo, root):
    """Computations called, transitively, from computation ``root``."""
    calls = {}
    comp = None
    for line in hlo.split("\n"):
        head = re.match(r"^(?:ENTRY )?%(\S+) .*\{$", line)
        if head:
            comp = head.group(1)
            calls[comp] = set()
        elif comp is not None:
            calls[comp].update(re.findall(
                r"(?:body|condition|calls|to_apply|branch_computations)="
                r"\{?%([\w.\-]+)", line))
    seen, todo = set(), [root]
    while todo:
        c = todo.pop()
        if c not in seen:
            seen.add(c)
            todo.extend(calls.get(c, ()))
    return seen


@pytest.fixture(scope="module", params=[
    ("reversible", "moment_mse"), ("reversible", "signature_mmd"),
    ("full", "moment_mse"), ("full", "signature_mmd"),
    ("reversible-paths", "moment_mse"), ("reversible-paths", "signature_mmd")],
    ids=lambda p: "-".join(p))
def compiled(request):
    return request.param, _compiled_hlo(*request.param)


def test_every_scope_names_some_instruction(compiled):
    (adjoint, _), hlo = compiled
    named = {_scope_of(p) for _, _, p, _ in _instructions(hlo)}
    expected = set(SCOPES) - ({"sde_reverse"} if adjoint == "full" else set())
    assert expected <= named, expected - named
    if adjoint == "full":
        assert "sde_reverse" not in named


def test_scanned_body_ops_carry_a_scope(compiled):
    (route, _), hlo = compiled
    entry = re.search(r"^ENTRY %(\S+) ", hlo, re.M).group(1)
    outer = [line for c, op, _, line in _instructions(hlo)
             if c == entry and op == "while"]
    assert len(outer) == 1
    body = re.search(r"body=%([\w.\-]+)", outer[0]).group(1)
    fused = set(re.findall(r"\bfusion\(.*?calls=%([\w.\-]+)", hlo))
    inside = _reachable(hlo, body) - fused
    ranked = [(op, p) for c, op, p, _ in _instructions(hlo)
              if c in inside and op in _RANKED]
    assert len(ranked) > 50
    unnamed = {p for op, p in ranked if _scope_of(p) is None}
    allowed = _UNNAMEABLE | (set() if route == "reversible-paths"
                             else _VMAP_BOUNDARY)
    assert unnamed <= allowed, unnamed - allowed
    # what no scope can name is a small part of the body
    n_unnamed = sum(_scope_of(p) is None for _, p in ranked)
    assert n_unnamed < 0.25 * len(ranked)
