"""Device time of a capture by the program's named scopes.

The program wraps the layer boundaries of its jitted train step in
``jax.named_scope`` (its list is ``euler_tpu.trace.STEP_SCOPES``). Which
names are scopes here is an open set: every reader under ``layers/``
that sums device time by scope states the scopes it claims as data, a
module-level ``SCOPES = ("gather_features", "gather_labels")``, and the
universe is their union (``declared_scopes``). A PR that names a new
scope in the program adds a reader file that claims it and edits
nothing; a test holds every scope of the program to exactly one reader,
so that the scope metrics add up to the device's busy time.
``train(profile_dir=)`` leaves the compiled step's HLO text beside the
capture. A TPU capture names an ``XLA Ops`` event by its instruction's HLO
text (``%fusion.3 = f32[...] fusion(...)``) and carries no ``op_name``
(PERF.md section 6, PR 27), so the way from an event to its scope is: the
event's leading ``%name`` -> that instruction in the HLO text -> the
``op_name`` of its metadata -> the innermost path component that is a
scope (``jit(train_step)/transpose(jvp(M))/aggregate/dense/dot_general``
is ``dense``: the backward pass rides its scope). A fusion whose own
``op_name`` names no scope takes the scope that most instructions of its
fused computation carry. What no scope claims, above all what the
compiler inserts itself, is ``unscoped``. Collectives are left out: they
have a metric of their own (``mesh.collective_ms``).

Imports nothing of the program. Where the capture has no HLO text beside
it (a program from before the scopes), every function here returns None.
"""

from __future__ import annotations

import ast
import functools
import os
import re
from collections import Counter

from benchmark import xplane

LAYERS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "layers")
STEP_HLO_FILE = "train_step.hlo.txt"
UNSCOPED = "unscoped"

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?(%?[\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"\bcalls=(%?[\w.\-]+)")
_COMPUTATION = re.compile(r"^\s*(?:ENTRY\s+)?(%?[\w.\-]+)\s.*\{\s*$")
_EVENT_NAME = re.compile(r"^\s*(%?[\w.\-]+)")


@functools.lru_cache(maxsize=8)
def declared_scopes(layers_dir: str = LAYERS_DIR) -> dict:
    """scope -> the reader (file name less ``.py``) that claims it, over
    the ``SCOPES`` tuples of the readers in ``layers_dir``. Read as data
    (no reader is imported). Two readers claiming one scope is an error:
    their metrics would count its time twice."""
    claimed: dict = {}
    for fn in sorted(os.listdir(layers_dir)):
        if not fn.endswith(".py"):
            continue
        with open(os.path.join(layers_dir, fn)) as f:
            tree = ast.parse(f.read(), fn)
        for node in tree.body:
            if isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == "SCOPES"
                for t in node.targets
            ):
                for scope in ast.literal_eval(node.value):
                    if scope in claimed:
                        raise ValueError(
                            f"scope {scope!r} is claimed by both "
                            f"{claimed[scope]} and {fn[:-3]}")
                    claimed[scope] = fn[:-3]
    return claimed


class _ScopeNames(tuple):
    """Equal to any sequence of the same names, in whatever order."""

    def __eq__(self, other):
        return sorted(self) == sorted(other)

    def __ne__(self, other):
        return not self == other

    __hash__ = tuple.__hash__


def __getattr__(name: str):
    # ``tests/test_step_tracing.py`` (outside the benchmark's paths, so
    # not a benchmark PR's to edit) still reads ``scopes.STEP_SCOPES`` and
    # holds it equal to the program's tuple. It is the readers' union
    # now; tests/benchmark/test_scopes.py holds the stronger pin. Goes
    # with that test (PERF.md section 7).
    if name == "STEP_SCOPES":
        return _ScopeNames(declared_scopes())
    raise AttributeError(name)


def scope_of_op_name(op_name: str, universe=None):
    """The innermost scope on an ``op_name`` path, or None. A fused op
    may carry several paths joined by ``;``: the first is its root's.
    ``universe``: the scope names (default: what the readers declare)."""
    if universe is None:
        universe = declared_scopes()
    path = op_name.split(";", 1)[0]
    for part in reversed(path.split("/")):
        if part in universe:
            return part
    return None


def _bare(name: str) -> str:
    return name.lstrip("%")


def parse_hlo_scopes(text: str, universe=None) -> dict:
    """instruction name (no ``%``) -> scope or ``unscoped``, for every
    instruction of every computation of an HLO module's text."""
    if universe is None:
        universe = declared_scopes()
    own: dict = {}        # instruction -> scope | None
    calls: dict = {}      # instruction -> called computation
    members: dict = {}    # computation -> [instruction]
    comp = None
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c and "=" not in line.split("{", 1)[0]:
                comp = _bare(c.group(1))
                members.setdefault(comp, [])
            continue
        name = _bare(m.group(1))
        op = _OP_NAME.search(line)
        own[name] = scope_of_op_name(op.group(1), universe) if op else None
        called = _CALLS.search(line)
        if called:
            calls[name] = _bare(called.group(1))
        if comp is not None:
            members[comp].append(name)
    out = {}
    for name, scope in own.items():
        if scope is None and name in calls:
            votes = Counter(
                own[i] for i in members.get(calls[name], ()) if own.get(i))
            if votes:
                scope = votes.most_common(1)[0][0]
        out[name] = scope or UNSCOPED
    return out


def hlo_path_for(xplane_path: str) -> str:
    """``<profile_dir>/train_step.hlo.txt`` for a capture at
    ``<profile_dir>/plugins/profile/<run>/<host>.xplane.pb``."""
    d = xplane_path
    for _ in range(4):
        d = os.path.dirname(d)
    return os.path.join(d, STEP_HLO_FILE)


@functools.lru_cache(maxsize=4)
def scope_table(hlo_path: str):
    if not os.path.isfile(hlo_path):
        return None
    with open(hlo_path) as f:
        return parse_hlo_scopes(f.read())


def lane_scope_seconds(lane, table: dict) -> dict:
    """scope -> seconds of one chip's ops; collectives under
    ``collective``, ops of no known instruction under ``unscoped``."""
    out: dict = {}
    for name, start, end in lane.events:
        if xplane.COLLECTIVE.search(name):
            scope = "collective"
        else:
            m = _EVENT_NAME.match(name)
            scope = table.get(_bare(m.group(1)), UNSCOPED) if m else UNSCOPED
        out[scope] = out.get(scope, 0.0) + (end - start) * 1e-9
    return out


def step_scope_ms(ctx):
    """scope -> device ms per traced step on the fullest chip, or None
    where the run left no capture or no HLO text. Kept on ``ctx``: five
    readers ask."""
    if getattr(ctx, "_scope_ms", None) is None:
        cap = ctx.capture
        table = scope_table(hlo_path_for(ctx.xplane_path)) if cap else None
        if table is None:
            return None
        ctx._scope_ms = {
            k: v * 1e3 / ctx.trace_steps
            for k, v in lane_scope_seconds(cap.fullest(), table).items()
        }
    return ctx._scope_ms


def scopes_ms(ctx, *names):
    """Sum of ``step_scope_ms`` over ``names``; None without a table."""
    ms = step_scope_ms(ctx)
    if ms is None:
        return None
    return sum(ms.get(n, 0.0) for n in names)
