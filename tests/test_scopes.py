"""Device scopes under the program's own names (ceph_tpu/trace/span.py:
``SCOPES``, ``scope``).

The table and the emit sites are held to each other; a name that is not
in the table raises where it is written; what ``scope`` opens is JAX's
name scope ``rados.<name>``, which reaches an instruction's ``op_name``
through a loop and a nested scope.  That the crush programs carry the
scopes, and that a scope adds no operation, is checked where those
programs are compiled anyway: tests/test_crush_rules_device.py (the
two-step indep pool program, the resolve program) and
tests/test_crush_device.py::TestDenseTail (firstn with a tail).
``scoped_share`` and ``scope_paths`` below read a compiled module's text
for them.
"""

import ast
import glob
import os
import re

import pytest

from benchmark.harness.device_scopes import path_of
from ceph_tpu.trace import span as spanmod
from ceph_tpu.trace.span import PREFIX, SCOPES, SPANS, scope

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# "%name = type opcode(operands), ..., metadata={op_name="jit(run)/..."}"
_INSTR = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = \S+ ([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


# what a pool program with a tail reaches of the table (a firstn rule's,
# an indep rule's of one step or two)
POOL_SCOPES = {"crush.seeds", "crush.first", "crush.tail.move",
               "crush.tail.rounds", "crush.step", "crush.descend",
               "crush.is_out", "crush.post"}


def scope_paths(hlo_text: str) -> dict:
    """path -> instructions of a compiled module's text, over the
    instructions the program traced: those whose op_name starts with the
    jitted function (``jit(run)/...``).  What the compiler made (tuples
    and their elements, copies, fusions' shells: no metadata) and the
    scalar bodies of reducers (``reduce_sum``, ``lt``) are no operation
    of the program; parameters and constants are left out.  A path is
    read as the benchmark's reader reads it off the device trace."""
    paths: dict = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m or m.group(1) in ("parameter", "constant"):
            continue
        name = _OP_NAME.search(line)
        if not name or not name.group(1).startswith("jit("):
            continue
        p = path_of(name.group(1))
        paths[p] = paths.get(p, 0) + 1
    return paths


def scoped_share(paths: dict) -> float:
    """Per cent of those instructions under some registered scope."""
    return 100.0 * sum(n for p, n in paths.items() if p) / sum(
        paths.values())


def assert_same_without_scopes(monkeypatch, with_scopes, shapes, build):
    """`with_scopes`: a crush program lowered for `shapes`; `build()`
    makes the same program from a mapper of its own, traced while
    `scope` is a null context.  Both lower to the same StableHLO: the
    same operations per opcode, in the same order.  Only lowered: no
    second compile."""
    import collections
    import contextlib

    from ceph_tpu.ops.crush import device as D

    class NoScope(contextlib.ContextDecorator):
        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    assert PREFIX + "crush." in with_scopes.as_text(debug_info=True)
    monkeypatch.setattr(D, "scope", lambda name: NoScope())
    for decorated in ("_descend", "_is_out"):   # wrapped at import
        monkeypatch.setattr(D, decorated, getattr(D, decorated).__wrapped__)
    without = build().lower(*shapes)
    assert PREFIX not in without.as_text(debug_info=True)

    def opcodes(lowered):
        return collections.Counter(re.findall(
            r"= \"?((?:stablehlo|func|chlo)\.[a-z_]+)", lowered.as_text()))

    assert sum(opcodes(without).values()) > 100
    assert opcodes(with_scopes) == opcodes(without)
    assert with_scopes.as_text() == without.as_text()


def _scope_calls():
    """(file:line, call node) of every ``scope(...)`` under ceph_tpu/,
    and file:line of every use of jax's own named_scope."""
    calls, raw = [], []
    for path in sorted(glob.glob(os.path.join(ROOT, "ceph_tpu", "**",
                                              "*.py"), recursive=True)):
        with open(path) as f:
            tree = ast.parse(f.read())
        rel = os.path.relpath(path, ROOT)
        for node in ast.walk(tree):
            at = "%s:%d" % (rel, getattr(node, "lineno", 0))
            if isinstance(node, ast.Attribute) and \
                    node.attr == "named_scope":
                raw.append(at)
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id == "scope"):
                calls.append((at, node))
    return calls, raw


CALLS, RAW = _scope_calls()
EMITTED: dict = {}
for _at, _node in CALLS:
    _arg = _node.args[0] if _node.args else None
    if isinstance(_arg, ast.Constant) and isinstance(_arg.value, str):
        EMITTED.setdefault(_arg.value, []).append(_at)


def test_every_name_scope_goes_through_scope_with_a_registered_literal():
    # the one jax.named_scope of the tree is the primitive's own
    assert len(RAW) == 1 and RAW[0].startswith(
        os.path.join("ceph_tpu", "trace", "span.py")), RAW
    for at, node in CALLS:
        arg = node.args[0] if node.args else None
        assert isinstance(arg, ast.Constant) and \
            isinstance(arg.value, str), \
            "%s: scope name must be a literal" % at
        assert len(node.args) == 1 and not node.keywords, at
    unknown = {n: at for n, at in EMITTED.items() if n not in SCOPES}
    assert not unknown, unknown


@pytest.mark.parametrize("name", sorted(SCOPES))
def test_registered_scope_is_emitted(name):
    layer, covers = SCOPES[name]
    assert layer == spanmod.KERNELS and covers
    # a host span and a device scope never share a name: the readers
    # tell them apart by where they stand, a reader of text by the name
    assert name not in SPANS
    assert name in EMITTED, "%s is in SCOPES and emitted nowhere" % name


def test_an_unregistered_scope_raises_where_it_is_written():
    with pytest.raises(KeyError):
        scope("crush.no_such_stage")
    with pytest.raises(KeyError):
        scope("crush.launch")       # a host span's name is no scope


def test_a_scope_reaches_op_name_through_a_loop_and_a_nested_scope():
    """Lowered text names a loop body's scopes without those around the
    loop; the compiled module's op_name holds the whole path, which is
    what the device trace carries."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def run(x):
        with scope("crush.resolve.a"):
            def body(_i, c):
                with scope("crush.settle.draw"):
                    return jnp.sin(c) * 2.0
            x = jax.lax.fori_loop(0, 3, body, x)
        return jnp.tanh(x)

    x = jnp.ones((8, 128), jnp.float32)
    names = _OP_NAME.findall(run.lower(x).compile().as_text())
    assert any(path_of(n) == ("crush.resolve.a", "crush.settle.draw")
               and n.endswith("/sin") for n in names), names
    assert any(n.endswith("/tanh") and path_of(n) == () for n in names)
    # as a decorator, and entered twice: one path entry per level
    both = scope("crush.step")(lambda v: scope("crush.step")(jnp.cos)(v))
    text = jax.jit(both).lower(x).compile().as_text()
    assert ("crush.step", "crush.step") in {
        path_of(n) for n in _OP_NAME.findall(text)}
