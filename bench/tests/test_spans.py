"""The span reduction, on synthetic traces with known answers, on a
program compiled here and on the small trace recorded on a TPU v5e."""
import re
from pathlib import Path

import pytest

from bench import counting, harness, spans, trace

DATA = Path(__file__).parent / "data"


# ------------------------------------------------- a tiny protobuf writer --
def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields) -> bytes:
    """A message from (field number, int | str | bytes) pairs."""
    out = b""
    for num, val in fields:
        if isinstance(val, int):
            out += _varint(num << 3) + _varint(val)
        else:
            val = val.encode() if isinstance(val, str) else val
            out += _varint(num << 3 | 2) + _varint(len(val)) + val
    return out


def _hlo(module: str, computations: dict, calls=None) -> bytes:
    """An ``xla.HloProto``: {computation: {instruction: op_name}}, the
    computations numbered from 1 in order; ``calls`` {instruction:
    [computation]} gives the computations an instruction calls."""
    ids = {c: n + 1 for n, c in enumerate(computations)}
    calls = calls or {}

    def inst(i, o):
        called = b"".join(_varint(ids[c]) for c in calls.get(i, ()))
        return _msg((1, i), (2, "op"), (7, _msg((2, o))),
                    *([(38, called)] if called else []))
    comps = [(3, _msg((1, c), (5, ids[c]),
                      *[(2, inst(i, o)) for i, o in insts.items()]))
             for c, insts in computations.items()]
    return _msg((1, _msg((1, module), *comps)))


def _plane(name: str, lines=(), metadata=(), stats=(), stat_names=()):
    """An ``XPlane``: lines [(name, [(metadata id, start ns, end ns,
    {stat id: int})])], metadata [(id, name, [(stat id, bytes)])]."""
    out = [(2, name)]
    for ln, events in lines:
        evs = [(4, _msg((1, mid), (2, s * 1000), (3, (e - s) * 1000),
                        *[(4, _msg((1, k), (4, v)))
                          for k, v in (st or {}).items()]))
               for mid, s, e, st in events]
        out.append((3, _msg((2, ln), (3, 0), *evs)))
    for mid, mname, mstats in metadata:
        ems = [(5, _msg((1, k), (6, v))) for k, v in mstats]
        out.append((4, _msg((1, mid), (2, _msg((1, mid), (2, mname),
                                               *ems)))))
    for sid, sname in stat_names:
        out.append((5, _msg((1, sid), (2, _msg((1, sid), (2, sname))))))
    out += [(6, _msg((1, k), (3, v))) for k, v in stats]
    return _msg(*out)


def _xspace(programs: dict, ops: list, modules: list, host: list,
            length_ns: int = 30000) -> bytes:
    """programs {id: (module, {computation: {instruction: op_name}}[,
    calls])}; ops [(instruction, start, end)]; modules [(id, start, end)];
    host [(name, start, end, {arg: int})]."""
    names = sorted({i for i, _, _ in ops})
    op_meta = [(n + 1, f"%{i} = f32[2] op()", []) for n, i in
               enumerate(names)]
    mod_meta = [(100 + n, f"{programs[p][0]}({p})", [])
                for n, (p, _, _) in enumerate(modules)]
    device = _plane("/device:TPU:0", lines=[
        ("XLA Modules", [(100 + n, s, e, None)
                         for n, (_, s, e) in enumerate(modules)]),
        ("XLA Ops", [(names.index(i) + 1, s, e, None) for i, s, e in ops])],
        metadata=op_meta + mod_meta)
    meta = _plane("/host:metadata", metadata=[
        (p, f"{prog[0]}({p})", [(1, _hlo(*prog))])
        for p, prog in programs.items()],
        stat_names=[(1, "Hlo Proto")])
    args = sorted({k for _, _, _, a in host for k in a})
    host_names = sorted({n for n, _, _, _ in host})
    cpu = _plane("/host:CPU", lines=[("python", [
        (host_names.index(n) + 1, s, e,
         {args.index(k) + 1: v for k, v in a.items()})
        for n, s, e, a in host])],
        metadata=[(i + 1, n, []) for i, n in enumerate(host_names)],
        stat_names=[(i + 1, k) for i, k in enumerate(args)])
    env = _plane("Task Environment", stats=[(1, 0), (2, length_ns)],
                 stat_names=[(1, "profile_start_time"),
                             (2, "profile_stop_time")])
    return _msg((1, device), (1, meta), (1, cpu), (1, env))


STEP = {"main": {"while.7": "jit(step)/while",
                 "fusion.1": "jit(step)/while/body/round.sgd/dot",
                 "fusion.2": "jit(step)/while/body/transpose(jvp(round.sgd))"
                             "/mul",
                 "_fedavg_reduce.1": "jit(step)/round.aggregate/pallas_call",
                 "copy.3": ""},
        "fused_computation": {"mul.5": "jit(step)/vmap(round.channel)/mul"}}
OTHER = {"main": {"fusion.1": "jit(other)/vmap(round.eval)/reduce"}}
OPS = [("while.7", 500, 9000), ("fusion.1", 1000, 4000),
       ("fusion.2", 4000, 5000), ("_fedavg_reduce.1", 6000, 8000),
       ("copy.3", 8000, 8500), ("fusion.1", 12000, 13000)]
MODULES = [(7, 0, 10000), (9, 12000, 14000)]
HOST = [("fl.call", 0, 20000, {"round": 4}),
        ("fl.dispatch", 0, 5500, {"round": 4, "rounds": 3}),
        ("fl.fetch", 11000, 19000, {"round": 4}),
        ("host_work", 9000, 10000, {})]

# XLA-inserted copies in loop bodies: the SGD loop's body is called by a
# while under round.sgd; the inner body's caller has no op_name either, so
# its copy looks one caller further out; the round loop's names no span
NESTED = {"main": {"while.1": "jit(step)/while", "copy.1": ""},
          "round_body": {"while.2": "jit(step)/while/body/round.sgd/while",
                         "copy.2": ""},
          "sgd_body": {"copy.3": "", "while.3": "",
                       "fusion.4": "jit(step)/while/body/round.sgd/while/"
                                   "body/dot"},
          "inner_body": {"copy.4": ""}}
NESTED_CALLS = {"while.1": ["round_body"], "while.2": ["sgd_body"],
                "while.3": ["inner_body"]}
NESTED_OPS = [("copy.1", 0, 100), ("copy.2", 100, 300),
              ("copy.3", 300, 700), ("fusion.4", 700, 1500),
              ("copy.4", 1500, 1600)]


def _write(tmp_path, data: bytes) -> str:
    d = tmp_path / "cell-1" / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    path = d / "host.xplane.pb"
    path.write_bytes(data)
    return str(path)


def test_innermost_span_of_an_op_name():
    assert spans.innermost("jit(f)/round.select/while/body/dagsa.eq11/"
                           "pallas_call") == "dagsa.eq11"
    assert spans.innermost("jit(f)/vmap(vmap(world.init))/jit(_uniform)"
                           "/add") == "world.init"
    assert spans.innermost("jit(f)/transpose(jvp(round.sgd))/mul") == \
        "round.sgd"
    assert spans.innermost("jit(f)/round.sgdx/mul") == spans.NO_SPAN
    assert spans.innermost("") == spans.NO_SPAN


def test_device_time_goes_to_the_innermost_span_of_its_program(tmp_path):
    path = _write(tmp_path, _xspace(
        {7: ("jit_step", STEP), 9: ("jit_other", OTHER)}, OPS, MODULES,
        HOST))
    s = spans.reduce(path)
    # leaves [1000, 5000), [6000, 8500), [12000, 13000)
    assert s.busy_s == pytest.approx(7500e-9)
    assert s.busy_s == pytest.approx(trace.reduce(path, window_s=1).busy_s)
    # fusion.1 is round.sgd in program 7 and round.eval in program 9; the
    # while's self time (8500 - 6500) and the bare copy, which no
    # instruction calls, have no span
    assert s.device_s == pytest.approx({
        "round.sgd": 4000e-9, "round.aggregate": 2000e-9,
        "round.eval": 1000e-9, spans.NO_SPAN: 2500e-9})
    assert s.unnamed_s == pytest.approx(500e-9)         # the copy
    assert s.unresolved_s == 0.0
    # a span only inside a fused computation is still in the program
    assert s.present == {"round.sgd", "round.aggregate", "round.channel",
                         "round.eval"}


def test_an_op_with_no_op_name_takes_the_span_of_its_caller(tmp_path):
    names = spans.hlo_op_names(_hlo("jit_step", NESTED, NESTED_CALLS))
    assert names["copy.1"] == ("", True)
    assert names["copy.2"] == ("jit(step)/while", True)
    assert names["copy.3"] == ("jit(step)/while/body/round.sgd/while", True)
    assert names["copy.4"] == names["copy.3"]   # through while.3, unnamed
    assert names["fusion.4"][1] is False
    path = _write(tmp_path, _xspace(
        {5: ("jit_step", NESTED, NESTED_CALLS)}, NESTED_OPS, [(5, 0, 2000)],
        []))
    s = spans.reduce(path)
    assert s.device_s == pytest.approx({"round.sgd": 1300e-9,
                                        spans.NO_SPAN: 300e-9})
    assert s.unnamed_s == pytest.approx(800e-9)         # the copies


def test_a_caller_loop_does_not_hang_the_inheritance():
    comps = {"a": {"call.1": "", "copy.1": ""}, "b": {"call.2": ""}}
    names = spans.hlo_op_names(_hlo("m", comps, {"call.1": ["b"],
                                                 "call.2": ["a"]}))
    assert names["copy.1"] == ("", True)


def test_idle_gaps_split_by_the_innermost_host_span(tmp_path):
    path = _write(tmp_path, _xspace(
        {7: ("jit_step", STEP), 9: ("jit_other", OTHER)}, OPS, MODULES,
        HOST))
    s = spans.reduce(path)
    # gaps [5000, 6000), [8500, 12000) and [13000, 30000) (profile end);
    # host_work is no span of the program's
    assert s.idle_s == pytest.approx({
        "fl.dispatch": 500e-9, "fl.call": 4000e-9, "fl.fetch": 7000e-9,
        spans.NO_SPAN: 10000e-9})


def test_ops_no_traced_program_names_are_counted(tmp_path):
    path = _write(tmp_path, _xspace(
        {7: ("jit_step", {"main": {"fusion.1": STEP["main"]["fusion.1"]}})},
        OPS[:5], MODULES[:1], HOST))
    s = spans.reduce(path)
    assert s.device_s["round.sgd"] == pytest.approx(3000e-9)
    # fusion.2, the kernel, the copy and the while's self time
    assert s.unresolved_s == pytest.approx(5500e-9)


def test_a_compiled_loop_body_copy_is_found_under_its_loops_span():
    """The HLO XLA itself writes: ids, called computations and the copies
    it inserts with no op_name."""
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("round.sgd"):
            return jax.lax.while_loop(
                lambda c: c[0] < 3,
                lambda c: (c[0] + 1, jnp.sin(c[1]) @ c[1].T), (0, x))[1]
    compiled = jax.jit(f).lower(jnp.ones((8, 8))).compile()
    module = compiled.runtime_executable().hlo_modules()[0]
    names = spans.hlo_op_names(
        _msg((1, module.as_serialized_hlo_module_proto())))
    assert {spans.innermost(o) for o, _ in names.values()} == \
        {"round.sgd", spans.NO_SPAN}
    text = compiled.as_text()
    body = [i for i, (o, up) in names.items()
            if up and spans.innermost(o) == "round.sgd"
            and f"{i} = " in text]
    assert body, names


def test_the_command_prints_the_breakdown(tmp_path, capsys):
    _write(tmp_path, _xspace(
        {7: ("jit_step", STEP), 9: ("jit_other", OTHER)}, OPS, MODULES,
        HOST))
    assert spans.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out.splitlines()
    assert out[0].endswith("host.xplane.pb")
    assert out[2].startswith("device seconds by span (share of busy "
                             "time): round.sgd 4e-06 (53.33%), ")
    assert "fl.fetch 7e-06" in out[3]
    assert out[6:] == [
        "longest operations by span (self seconds):",
        "  round.sgd: fusion.1 3e-06, fusion.2 1e-06",
        "  (no span): while.7 2e-06, copy.3* 5e-07",
        "  round.aggregate: _fedavg_reduce.1 2e-06",
        "  round.eval: fusion.1 1e-06"]
    assert spans.main([]) == 2


def test_recorded_tpu_trace_resolves_every_op():
    """The small trace recorded on a TPU v5e holds each program's HLO: all
    of its device time is found, none of it under a span."""
    path = str(DATA / "small.xplane.pb")
    s = spans.reduce(path)
    t = trace.reduce(path)
    assert s.busy_s == t.busy_s
    assert s.device_s == {spans.NO_SPAN: pytest.approx(
        sum(t.op_seconds.values()))}
    assert s.present == set()
    assert s.unresolved_s == 0.0
    names = spans.program_op_names(open(path, "rb").read())
    assert len(names) == 4
    assert all("_fedavg_reduce.1" in n for n in names.values()
               if any("fedavg" in o for o, _ in n.values()))


def test_existing_readings_of_the_recorded_trace_are_unchanged():
    calls = counting.kernel_calls(
        (DATA / "small_fedavg_hlo.txt").read_text(), ["_fedavg_kernel"])
    s = trace.reduce(str(DATA / "small.xplane.pb"), ["_fedavg_kernel"],
                     calls)
    assert s.busy_s == pytest.approx(1.779e-05)
    assert s.kernel_seconds == {"_fedavg_kernel": pytest.approx(6.26e-07)}
    ctx = harness.Context(
        cell=harness.find_cell("paper-cnn.sync"), setup_s=1.0,
        window_s=s.window_s, units=3, peak_bytes=1,
        counters={"rounds": 3, "n_selected": [50, 25, 26]},
        peaks=harness.device_peaks("TPU v5 lite"), trace=s,
        kernel_calls=calls)

    def read(metric):
        return harness.load_module("metrics", metric).read(ctx)
    assert read("fedavg_reduce_roofline") == \
        pytest.approx(15.316738639422343)
    assert read("idle_share.learning") == pytest.approx(99.99430892325803)
    assert read("mfu.learning") == pytest.approx(4.232036436203666)


def test_a_cells_spans_list_attributes_a_new_scope(tmp_path):
    """A scope the base vocabulary lacks is booked under its caller's span;
    a cell that lists it in its ``spans`` gets its time apart, and the rest
    reduces as under the base vocabulary."""
    step = {"main": dict(STEP["main"], **{
        "fusion.1": "jit(step)/while/body/round.sgd/lm.attn/dot"})}
    path = _write(tmp_path, _xspace({7: ("jit_step", step)}, OPS[:5],
                                    MODULES[:1], HOST))
    base = spans.reduce(path)
    names = spans.vocabulary({"spans": ["lm.attn", "round.sgd"]}, {})
    assert names == spans.SPANS + ("lm.attn",)
    own = spans.reduce(path, names)
    assert base.device_s["round.sgd"] == pytest.approx(4000e-9)
    assert own.device_s["lm.attn"] == pytest.approx(3000e-9)
    assert own.device_s["round.sgd"] == pytest.approx(1000e-9)
    assert "lm.attn" in own.present and "lm.attn" not in base.present
    rest = {k: v for k, v in own.device_s.items()
            if k not in ("lm.attn", "round.sgd")}
    assert rest == {k: v for k, v in base.device_s.items()
                    if k != "round.sgd"}
    assert spans.vocabulary({}, {}) == spans.SPANS


def _printed_device_seconds(out: str) -> dict:
    line = next(ln for ln in out.splitlines()
                if ln.startswith("device seconds by span"))
    pairs = (item.rsplit(" (", 1)[0].rsplit(" ", 1)
             for item in re.split(r"%\), ", line.split(": ", 1)[1]))
    return {name: float(v) for name, v in pairs}


@pytest.mark.parametrize("recorded", [True, False],
                         ids=["tpu-trace", "synthetic"])
def test_the_harness_reads_what_the_command_prints(recorded, tmp_path,
                                                   capsys):
    """``ctx.span_seconds`` of a traced run is ``bench.spans.reduce``'s, in
    the cell's vocabulary: what the command prints for the same trace."""
    path = str(DATA / "small.xplane.pb") if recorded else _write(
        tmp_path, _xspace({7: ("jit_step", STEP), 9: ("jit_other", OTHER)},
                          OPS, MODULES, HOST))
    cell = harness.find_cell("paper-cnn.sync")
    _, by_span = harness.reduce_trace(path, cell, [], {}, None)
    ctx = harness.Context(cell=cell, setup_s=1.0, window_s=1.0, units=1,
                          peak_bytes=1, counters={}, peaks={},
                          spans=by_span)
    assert spans.main([path, "--workload", cell.name]) == 0
    assert _printed_device_seconds(capsys.readouterr().out) == \
        ctx.span_seconds
    assert spans.main([path, "--workload"]) == 2
