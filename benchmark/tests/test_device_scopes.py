"""The readers of the device's name scopes, on a hand-made list (no
profiler, no protobuf).  Not tier-1:

    python3 -m pytest benchmark/tests/test_device_scopes.py -q -p no:cacheprovider
"""

import pytest

from benchmark.harness import device_scopes
from benchmark.readers import device_scope_cover, device_scope_per
from benchmark.tests.device_scopes import kind

MS = 1_000_000
A, DRAW, SCATTER = "rados.crush.resolve.a", "rados.crush.settle.draw", \
    "rados.crush.settle.scatter"


def made_up(scoped: bool = True, stage_b: bool = False) -> dict:
    """A 100 ms window on one device: a first-round descent of 20 ms; a
    `while` of 30 ms (no op_name) whose body is a stage-A descent of 10
    ms twice and a fusion of 4 ms; a scatter of 6 ms in stage A; an
    unscoped copy of 5 ms; a descent that straddles the window's end (8
    of its 20 ms inside) and one before the window."""
    ops = {
        "%descend.1 = custom-call()": "jit(run)/while/body/rados.crush."
        "first/rados.crush.descend/crush_straw2_descend/pallas_call:",
        "%while.2 = while()": "",
        "%descend.3 = custom-call()": "jit(run)/%s/%s/while/body/"
        "closed_call/rados.crush.first/rados.crush.descend/pallas_call:"
        % (A, DRAW),
        "%fusion.4 = fusion()": "jit(run)/%s/%s/while/body/closed_call/"
        "rados.crush.first/rados.crush.is_out/and:" % (A, DRAW),
        "%fusion.5 = fusion()": "jit(run)/%s/%s/scatter:" % (A, SCATTER),
        "%copy.6 = copy()": "",
        "%descend.7 = custom-call()": "jit(run)/rados.crush.tail.rounds/"
        "while/body/rados.crush.descend/pallas_call:",
    }
    events = [
        ("%descend.7 = custom-call()", -30 * MS, -10 * MS),
        ("%descend.1 = custom-call()", 0, 20 * MS),
        ("%while.2 = while()", 20 * MS, 50 * MS),
        ("%descend.3 = custom-call()", 21 * MS, 31 * MS),
        ("%fusion.4 = fusion()", 31 * MS, 35 * MS),
        ("%descend.3 = custom-call()", 36 * MS, 46 * MS),
        ("%fusion.5 = fusion()", 50 * MS, 56 * MS),
        ("%copy.6 = copy()", 60 * MS, 65 * MS),
        ("%descend.7 = custom-call()", 92 * MS, 112 * MS),
    ]
    if stage_b:
        ops["%fusion.8 = fusion()"] = \
            "jit(run)/rados.crush.resolve.b/%s/add:" % DRAW
        events.append(("%fusion.8 = fusion()", 70 * MS, 71 * MS))
    if not scoped:      # the parent: scopes of other names, no prefix
        ops = {k: v.replace("rados.", "crush_") for k, v in ops.items()}
    return {"window": (0, 100 * MS), "planes": [events], "op_names": ops}


def the_run(remaps=2, **made) -> dict:
    return {"trace": {"busy_s": 0.069}, "facts": {"remaps": remaps},
            "device_scopes": made_up(**made)}


def per(under, not_under=(), **made):
    return device_scope_per.read(
        {"under": list(under), "not_under": list(not_under),
         "per": "facts.remaps", "scale": 1e-6}, the_run(**made))


def test_a_path_is_the_programs_scopes_outermost_first():
    assert device_scopes.path_of(
        "jit(run)/%s/%s/while/body/closed_call/rados.crush.descend/"
        "pallas_call:" % (A, DRAW)) == (
            "crush.resolve.a", "crush.settle.draw", "crush.descend")
    assert device_scopes.path_of("jit(run)/crush_post/mul:") == ()
    assert device_scopes.path_of("") == ()


def test_a_while_is_not_charged_for_its_body():
    # the loop's 30 ms: 20 of descents and 4 of a fusion in stage A; the
    # 6 ms between them are the loop's own and have no path
    assert per(["crush.resolve.a"]) == pytest.approx((20 + 4 + 6) / 2)
    by_op = device_scopes.scopes_of(the_run())["by_op"]
    assert by_op[(), "%while.2 = while()"] == 6 * MS


def test_an_operation_under_two_of_the_scopes_counts_once():
    # the stage's descents stand under crush.first and crush.descend
    assert per(["crush.first", "crush.descend"]) == pytest.approx(
        (20 + 20 + 4 + 8) / 2)
    assert per(["crush.descend"]) == pytest.approx((20 + 20 + 8) / 2)


def test_not_under_takes_the_resolve_chains_rounds_out_of_first():
    assert per(["crush.first"]) == pytest.approx((20 + 20 + 4) / 2)
    assert per(["crush.first"], ["crush.resolve.a", "crush.resolve.b"]
               ) == pytest.approx(20 / 2)


def test_operations_are_clipped_to_the_window():
    # 8 of the straddling descent's 20 ms; the one before it not at all
    assert per(["crush.tail.rounds"]) == pytest.approx(8 / 2)


def test_the_parents_trace_reads_nothing():
    assert per(["crush.first"], scoped=False) is None
    assert device_scope_cover.read({}, the_run(scoped=False)) is None
    untraced = dict(the_run(), trace=None)
    assert device_scope_per.read(
        {"under": ["crush.first"], "per": "facts.remaps"}, untraced) is None
    assert device_scope_cover.read({}, untraced) is None
    # no unit to divide by
    assert per(["crush.first"], remaps=0) is None


def test_a_stage_that_never_ran_reads_zero():
    assert per(["crush.resolve.b", "crush.resolve.c"]) == 0
    assert per(["crush.resolve.b", "crush.resolve.c"],
               stage_b=True) == pytest.approx(0.5)


def test_cover_leaves_out_what_has_no_scope():
    # busy: 0-56, 60-65, 92-100 = 69 ms; unscoped: the loop's own 6 and
    # the copy's 5
    assert device_scope_cover.read({}, the_run()) == pytest.approx(
        100.0 * (69 - 11) / 69)


def test_two_planes_are_averaged():
    run = the_run()
    run["device_scopes"]["planes"].append([])
    assert device_scope_per.read(
        {"under": ["crush.settle.scatter"], "per": "facts.remaps",
         "scale": 1e-6}, run) == pytest.approx(6 / 2 / 2)


def test_the_tools_operation_kind_drops_xlas_numbering():
    assert kind("%crush_straw2_descend.72 = u32[3,131072]{1,0} "
                "custom-call(u32[4] %x), custom_call_target="
                "\"tpu_custom_call\"") == "crush_straw2_descend"
    assert kind("%fusion.6255 = s32[8]{0} fusion(s32[8] %p), kind=kLoop"
                ) == "fusion"
    assert kind("%while.2 = (u32[]) while((u32[]) %t), condition=%c"
                ) == "while"
