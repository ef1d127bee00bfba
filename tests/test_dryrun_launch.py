"""Dry-run machinery tests: lower+compile on a small host-device mesh in a
SUBPROCESS (jax pins the device count at first init, so the 8-device test
must not contaminate the main test process)."""
import json
import os
import subprocess
import sys
import textwrap


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent("""
    import json, sys
    import repro.launch.dryrun as D
    import repro.launch.mesh as M
    M.make_production_mesh = (
        lambda multi_pod=False: M.make_mesh((2,2,2), ("pod","data","model"))
        if multi_pod else M.make_mesh((2,4), ("data","model")))
    D.make_production_mesh = M.make_production_mesh
    import repro.configs.base as CB
    CB.INPUT_SHAPES["train_4k"] = CB.InputShape("train_4k", 256, 8, "train")
    CB.INPUT_SHAPES["prefill_32k"] = CB.InputShape(
        "prefill_32k", 512, 8, "prefill")
    CB.INPUT_SHAPES["decode_32k"] = CB.InputShape(
        "decode_32k", 1024, 8, "decode")
    CB.INPUT_SHAPES["long_500k"] = CB.InputShape(
        "long_500k", 4096, 1, "decode")
    out = {}
    for arch, shape, mp in json.loads(sys.argv[1]):
        rec = D.run_one(arch, shape, multi_pod=mp)
        out[f"{arch}|{shape}|{mp}"] = {
            "flops": rec["flops"],
            "coll": {k: v for k, v in rec["collectives"].items()
                     if k != "_counts"},
            "peak": rec["memory"].get("peak_memory_in_bytes", 0),
        }
    print("RESULT" + json.dumps(out))
""")


def _run(combos):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT, json.dumps(combos)],
        capture_output=True, text=True, timeout=540, env=env, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines()
            if l.startswith("RESULT")][0]
    return json.loads(line[len("RESULT"):])


def test_dense_train_and_decode_lower_on_mesh():
    out = _run([["tinyllama-1.1b", "train_4k", False],
                ["tinyllama-1.1b", "decode_32k", False]])
    tr = out["tinyllama-1.1b|train_4k|False"]
    assert tr["flops"] > 1e9
    assert "all-reduce" in tr["coll"]  # zone gradient reduction exists
    de = out["tinyllama-1.1b|decode_32k|False"]
    assert de["flops"] > 1e6


def test_moe_expert_parallel_lowers():
    out = _run([["qwen3-moe-30b-a3b", "train_4k", False]])
    rec = out["qwen3-moe-30b-a3b|train_4k|False"]
    # expert-parallel psum + ZeRO gathers must appear
    assert rec["coll"].get("all-reduce", 0) > 0
    assert rec["coll"].get("all-gather", 0) > 0


def test_multi_pod_mesh_shards_pod_axis():
    out = _run([["tinyllama-1.1b", "train_4k", True]])
    rec = out["tinyllama-1.1b|train_4k|True"]
    assert rec["flops"] > 0


def test_hybrid_long_context_decode_lowers():
    out = _run([["recurrentgemma-9b", "long_500k", True],
                ["gemma3-12b", "long_500k", False]])
    for k, rec in out.items():
        assert rec["flops"] > 0, k


def test_collective_parser():
    from repro.launch.dryrun import parse_collective_bytes

    hlo = """
      %all-reduce.1 = f32[64,512]{1,0} all-reduce(%dot), channel_id=1
      %ag = bf16[8,128]{1,0} all-gather(%p0), dimensions={0}
      %fusion.2 = f32[2,2]{1,0} fusion(%all-reduce.1, %c), kind=kLoop
      %rs = (f32[16]{0}, f32[16]{0}) reduce-scatter(%a, %b), dims={0}
      %cp-start = bf16[4]{0} collective-permute-start(%x)
    """
    out = parse_collective_bytes(hlo)
    assert out["all-reduce"] == 64 * 512 * 4
    assert out["all-gather"] == 8 * 128 * 2
    assert out["reduce-scatter"] == 2 * 16 * 4
    assert out["collective-permute"] == 4 * 2
    # the fusion operand mention must NOT be counted
    assert out["_counts"]["all-reduce"] == 1


def test_param_spec_rules():
    """Sharding rules: divisibility fallback + expected axes (no devices
    needed — specs are pure metadata)."""
    import numpy as np

    import jax
    from repro.configs import get_config
    from repro.launch.sharding import param_spec

    class FakeMesh:
        shape = {"data": 16, "model": 16}

    cfg = get_config("qwen2-7b")
    # scan-stacked leaf: (repeats, H*hd, d)
    leaf = jax.ShapeDtypeStruct((28, 28 * 128, 3584), np.float32)
    spec = param_spec("layers/0/mix/wo", leaf, cfg, FakeMesh(), ("data",))
    assert spec == jax.sharding.PartitionSpec(None, "model", ("data",))
    # whisper vocab 51866 % 16 != 0 → replicate that dim
    wcfg = get_config("whisper-large-v3")
    emb = jax.ShapeDtypeStruct((51866, 1280), np.float32)
    spec = param_spec("embed", emb, wcfg, FakeMesh(), ("data",))
    assert spec[0] is None
