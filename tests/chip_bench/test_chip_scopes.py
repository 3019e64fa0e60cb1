"""Work counts from the config's reference module, device time per named
scope, and each scope's share of its roofline: on hand-built HLO and
traces whose answers were counted by hand, on a toy family added as new
files only, and on a traced dpr window recorded on a TPU v5e."""
import gzip
import json
import os
import shutil
import subprocess
import sys

import pytest

import cell
import devtrace
import flops
import peaks
from conftest import CHIP
from scopes import roofline_share

DATA = os.path.join(CHIP, "testdata")
PROGRAMS = ("helix_train_step", "helix_eval_nll")
DENSE = {"embed", "norm", "attention", "mlp", "head", "loss", "optimizer"}


# --- a protobuf writer, enough for hand-built HLO modules -----------------

def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _msg(*fields):
    """``(number, value)`` pairs: an int is a varint, bytes or str a
    length-delimited field, a list of ints a packed field."""
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += _varint(num << 3) + _varint(v)
        else:
            if isinstance(v, str):
                v = v.encode()
            elif isinstance(v, list):
                v = b"".join(_varint(x) for x in v)
            out += _varint(num << 3 | 2) + _varint(len(v)) + v
    return out


def _instr(id_, name, opcode, op_name, dims=(), calls=()):
    return _msg((1, name), (2, opcode), (3, _msg((3, list(dims)))),
                (7, _msg((2, op_name))), (35, id_),
                *([(38, list(calls))] if calls else []))


def _computation(id_, name, root, *instrs):
    return _msg((1, name), *[(2, i) for i in instrs], (5, id_), (6, root))


def _module():
    """fusion.1 fuses a dot under ``mlp`` with the residual add outside
    any scope; fusion.2 fuses the transposed dot of ``attention``'s
    backward with a smaller dot of ``head``; fusion.3 holds no dot and its
    root is under ``norm``; copy.4 ran under no scope; the dot.5 of the
    entry computation is under ``optimizer``."""
    fused1 = _computation(
        1, "fused_computation.1", 12,
        _instr(10, "param_0", "parameter", ""),
        _instr(11, "convolution.1", "convolution",
               "jit(f)/jvp()/while/body/closed_call/mlp/dot_general",
               (2, 8)),
        _instr(12, "add.1", "add", "jit(f)/jvp()/while/body/add", (2, 8)))
    fused2 = _computation(
        2, "fused_computation.2", 22,
        _instr(20, "dot.2a", "dot", "jit(f)/transpose(jvp(head))/dot_general",
               (4,)),
        _instr(21, "dot.2b", "dot",
               "jit(f)/transpose(jvp())/while/body/closed_call/checkpoint/"
               "rematted_computation/attention/bqhk,bshk->bhqs",
               (16, 16)),
        _instr(22, "tuple.2", "tuple", ""))
    fused3 = _computation(
        3, "fused_computation.3", 31,
        _instr(30, "multiply.3", "multiply", "jit(f)/mlp/mul", (8,)),
        _instr(31, "convert.3", "convert", "jit(f)/jvp(norm)/convert", (8,)))
    entry = _computation(
        4, "main", 44,
        _instr(40, "fusion.1", "fusion", "jit(f)/jvp()/while/body/add",
               (2, 8), (1,)),
        _instr(41, "fusion.2", "fusion", "", (), (2,)),
        _instr(42, "fusion.3", "fusion", "jit(f)/mlp/mul", (8,), (3,)),
        _instr(43, "copy.4", "copy", "jit(f)/while/body/copy", (8,)),
        _instr(44, "dot.5", "dot", "jit(f)/optimizer/dot_general", (8,)))
    return _msg((1, "m"), *[(3, c) for c in (fused1, fused2, fused3, entry)])


def test_hlo_scopes_follow_each_fusions_costliest_instruction():
    got = devtrace.hlo_scopes(_module(), DENSE)
    assert got == {"fusion.1": "mlp",         # the dot, not the root's add
                   "fusion.2": "attention",   # the larger of two dots
                   "fusion.3": "norm",        # no dot: its root
                   "dot.5": "optimizer", "convolution.1": "mlp",
                   "dot.2a": "head", "dot.2b": "attention",
                   "multiply.3": "mlp", "convert.3": "norm"}
    assert "copy.4" not in got                # reduce counts it as other


@pytest.mark.parametrize("op_name, scope", [
    ("jit(helix_train_step)/transpose(jvp())/while/body/closed_call/"
     "checkpoint/rematted_computation/attention/while/body/closed_call",
     "attention"),
    ("jit(helix_train_step)/transpose(jvp(head))/bsd,dv->bsv", "head"),
    ("jit(helix_train_step)/jvp(norm)/convert_element_type", "norm"),
    ("jit(helix_train_step)/jvp()/while/body/closed_call/mlp/jit(silu)",
     "mlp"),
    ("jit(helix_train_step)/optimizer/reduce_sum:", "optimizer"),
    ("jit(helix_eval_nll)/while/body/closed_call/while/body/dynamic_slice",
     None),
    ("jit(helix_train_step)/transpose(jvp())/while", None),
    ("jit(f)/while/body/copy;jit(f)/jvp(loss)/reduce_max", "loss"),
    ("", None),
])
def test_name_stacks_resolve_to_the_innermost_scope(op_name, scope):
    assert devtrace.scope_of(op_name, DENSE) == scope


def _octal(b: bytes) -> str:
    return "".join(f"\\{x:03o}" for x in b)


def _hand_trace(path):
    """A 10 ms window. helix_train_step(7) runs at 1-5 ms: fusion.1 (mlp)
    1-3 ms, copy.4 (other) 3-3.5 ms, fusion.2 (attention) 3.5-4.5 ms;
    its run at 8-12 ms ends past the window and is left out."""
    from jax.profiler import ProfileData
    ops = {1: "%fusion.1 = f32[2,8] fusion()", 2: "%copy.4 = f32[8] copy()",
           3: "%fusion.2 = f32[4] fusion()",
           4: "jit_helix_train_step(7)"}
    meta = "".join(f'event_metadata {{ key: {k} value {{ id: {k} '
                   f'name: "{v}" }} }}\n' for k, v in ops.items())

    def ev(k, start_ms, end_ms):
        return (f"events {{ metadata_id: {k} offset_ps: {int(start_ms * 1e9)}"
                f" duration_ps: {int((end_ms - start_ms) * 1e9)} }}")

    text = f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0
    {ev(4, 1, 5)} {ev(4, 8, 12)} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0
    {ev(1, 1, 3)} {ev(2, 3, 3.5)} {ev(3, 3.5, 4.5)} {ev(1, 8, 9)} }}
  {meta}
}}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python3" timestamp_ns: 0 {ev(1, 0, 10)} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench.trace_window" }} }}
}}
planes {{ id: 3 name: "/host:metadata"
  event_metadata {{ key: 1 value {{ id: 1 name: "jit_helix_train_step(7)"
    stats {{ metadata_id: 1 bytes_value: "{_octal(_msg((1, _module())))}" }}
  }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "Hlo Proto" }} }}
}}
"""
    with open(path, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(text))


def test_hand_built_trace_attributes_device_time_to_scopes(tmp_path):
    path = str(tmp_path / "t.xplane.pb")
    _hand_trace(path)
    r = devtrace.reduce(path, PROGRAMS, DENSE)
    assert r["programs"]["helix_train_step"][0] == 2     # as before
    got = r["scopes"]["helix_train_step"]
    assert got["runs"] == 1
    assert got["seconds"] == {"mlp": pytest.approx(0.002),
                              "other": pytest.approx(0.0005),
                              "attention": pytest.approx(0.001)}
    assert "scopes" not in devtrace.reduce(path, PROGRAMS)


def _run(scopes, runs=2, work=None):
    return {"trace": {"scopes": {"helix_train_step": {
                "runs": runs, "seconds": scopes}}},
            "peak": {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0},
            "work": {"helix_train_step": work or {
                "mlp": (50.0, 2.0), "optimizer": (0.0, 4.0),
                "head": (20.0, 1.0), "loss": (0.0, 5.0)}}}


def test_roofline_share_by_hand():
    run = _run({"mlp": 2.0, "optimizer": 1.0, "head": 0.5, "loss": 0.5})
    # mlp: max(50/100, 2/10) = 0.5 s a run, 2 runs in 2 s.
    assert roofline_share(run, "mlp") == pytest.approx(50.0)
    # optimizer: max(0, 4/10) = 0.4 s a run, 2 runs in 1 s.
    assert roofline_share(run, "optimizer") == pytest.approx(80.0)
    # head and loss as one: max(20/100, 6/10) = 0.6 s a run, 2 in 1 s.
    assert roofline_share(run, "head", "loss") == pytest.approx(120.0)
    assert roofline_share(run, "attention") is None
    assert roofline_share(dict(run, peak=None), "mlp") is None
    assert roofline_share(dict(run, trace=None), "mlp") is None


def _dense_forward_per_token(c, seq):
    # The dense count as the harness had it before it read the reference
    # module: every matmul, causal attention over the lower triangle.
    d, f, h, kv = (c["hidden_size"], c["intermediate_size"],
                   c["num_attention_heads"], c["num_key_value_heads"])
    hd = d // h
    layer = (2 * d * h * hd + 2 * 2 * d * kv * hd + 2 * h * hd * d
             + 3 * 2 * d * f + 2 * 2 * h * hd * (seq + 1) / 2)
    return c["num_hidden_layers"] * layer + 2 * d * c["vocab_size"]


@pytest.mark.parametrize("program, total, times", [
    ("helix_train_step", flops.train_step, 3),
    ("helix_eval_nll", flops.eval_pass, 1)])
def test_dense_scope_flops_add_up_to_the_programs(program, total, times):
    c = cell.load("internlm2-1.8b-2l.dpr").config
    work = flops.scope_work(c, program, 2, 2048)
    # Exactly the numbers step.mfu read before the counts moved.
    assert total(c, 2, 2048) == times * _dense_forward_per_token(
        c, 2048) * 2 * 2048
    assert flops.forward_per_token(c, 2048) == _dense_forward_per_token(
        c, 2048)
    assert ("optimizer" in work) == (program == "helix_train_step")
    assert all(b > 0 for _, b in work.values())


def test_dense_optimizer_bytes_by_hand():
    # 22 bytes per bf16 parameter (read the parameter, its gradient and
    # both float32 moments; write the parameter and both moments), 28 per
    # float32 norm weight.
    c = cell.load("internlm2-1.8b-2l.dpr").config
    d, f, v, n = 2048, 8192, 92544, 2
    matrices = 2 * v * d + n * (d * 2048 + 2 * d * 1024 + 2048 * d
                                + 3 * d * f)
    vectors = (2 * n + 1) * d
    assert flops.scope_work(c, "helix_train_step", 2, 2048)["optimizer"] == (
        0.0, 22 * matrices + 28 * vectors)


TOY_CONFIG = {
    "name": "toy-ssm", "family": "ssm", "reference": "toy_ssm",
    "hidden_size": 8, "num_hidden_layers": 3, "chips": 1,
    "train": {"batch": 2, "seq_len": 16, "steps": 1, "peak_lr": 1e-3,
              "warmup_steps": 1, "clip_norm": 1.0, "adamw": {}}}

TOY_MODULE = '''
def scope_work(config, program, batch, seq):
    if program == "helix_eval_nll":
        return {"ssd": (7.0 * batch * seq, 3.0)}
    return {"ssd": (21.0 * batch * seq, 9.0), "optimizer": (0.0, 5.0)}
'''

PROBE = '''
import json, sys
sys.path.insert(0, "benchmarks/chip")
import cell, flops
c = cell.load("toy-ssm.dpr").config
out = {"train": flops.train_step(c, 2, 16), "eval": flops.eval_pass(c, 2, 16),
       "per_token": flops.forward_per_token(c, 16),
       "work": flops.scope_work(c, "helix_train_step", 2, 16)}
try:
    flops.train_step(dict(c, reference="bare"), 2, 16)
except TypeError as e:
    out["bare"] = str(e)
print(json.dumps(out))
'''


def test_another_family_is_read_from_new_files_only(tmp_path):
    # The harness as committed, plus a config and a reference module of
    # another family, and one module that counts nothing.
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    chip = tmp_path / "benchmarks" / "chip"
    (chip / "configs" / "toy-ssm.json").write_text(json.dumps(TOY_CONFIG))
    (chip / "reference" / "toy_ssm.py").write_text(TOY_MODULE)
    (chip / "reference" / "bare.py").write_text("def forward():\n    pass\n")
    p = subprocess.run([sys.executable, "-c", PROBE], cwd=tmp_path,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["train"] == 21.0 * 2 * 16
    assert got["eval"] == 7.0 * 2 * 16
    assert got["per_token"] == 7.0
    assert got["work"] == {"ssd": [21.0 * 32, 9.0], "optimizer": [0.0, 5.0]}
    assert "scope_work" in got["bare"]


@pytest.fixture(scope="module")
def v5e_dpr(tmp_path_factory):
    path = tmp_path_factory.mktemp("v5e") / "v5e_dpr.xplane.pb"
    with gzip.open(os.path.join(DATA, "v5e_dpr.xplane.pb.gz")) as src:
        path.write_bytes(src.read())
    c = cell.load("internlm2-1.8b-2l.dpr").config
    work = {p: flops.scope_work(c, p, 2, 2048) for p in PROGRAMS}
    trace = devtrace.reduce(str(path), PROGRAMS,
                            {s for w in work.values() for s in w})
    return {"trace": trace, "work": work, "peak": peaks.peak("TPU v5 lite")}


def test_recorded_v5e_dpr_trace_attributes_the_step(v5e_dpr):
    # One iteration cut from a traced internlm2-1.8b-2l.dpr window (TPU
    # v5 lite): four train steps and the eval, with the HLO of both
    # programs as the trace's metadata plane held it.
    got = v5e_dpr["trace"]["scopes"]
    assert got["helix_train_step"]["runs"] == 4
    assert got["helix_eval_nll"]["runs"] == 1
    secs = got["helix_train_step"]["seconds"]
    assert secs.get("other", 0.0) <= 0.1 * sum(secs.values())
    assert {"attention", "mlp", "head", "loss", "optimizer"} <= set(secs)
    # Every operation's time is somewhere: the scopes add up to the
    # programs' own spans (async copies overlap compute a little).
    for program, (runs, seconds) in v5e_dpr["trace"]["programs"].items():
        assert got[program]["runs"] == runs
        assert sum(got[program]["seconds"].values()) == pytest.approx(
            seconds, rel=0.01)


def test_zero_length_markers_do_not_hide_an_operation():
    # A loop holds its body; an async copy's zero-length start marker
    # inside a fusion does not make the fusion a holder.
    events = [("while", 0, 100), ("body", 10, 40), ("fusion", 200, 300),
              ("copy-start", 200, 200), ("done", 250, 250),
              ("overlap", 290, 310)]
    assert sorted(n for n, _, _ in devtrace._leaves(events)) == [
        "body", "copy-start", "done", "fusion", "overlap"]


@pytest.mark.parametrize("scopes", [("attention",), ("mlp",),
                                    ("head", "loss"), ("optimizer",)])
def test_recorded_v5e_dpr_roofline_shares_are_possible(v5e_dpr, scopes):
    share = roofline_share(v5e_dpr, *scopes)
    assert 0 < share <= 100
