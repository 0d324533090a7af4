"""Sharding rules + a subprocess mini-dry-run on 16 host devices (the
multi-device logic cannot run in-process: jax locks the device count)."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.configs import get_config
from repro.distributed.sharding import shard_spec_for_path


class _FakeMesh:
    def __init__(self, data=16, model=16):
        self.shape = {"data": data, "model": model}
        self.axis_names = ("data", "model")


MESH = _FakeMesh()


def test_rules_tp_and_fsdp():
    cfg = get_config("qwen3_8b")
    s = shard_spec_for_path("blocks/attn/q/w", (36, 4096, 4096), MESH, cfg)
    assert tuple(s) == (None, "data", "model")      # heads 32 % 16 == 0
    s = shard_spec_for_path("blocks/attn/k/w", (36, 4096, 1024), MESH, cfg)
    assert "model" not in tuple(s)                  # kv 8 % 16 != 0 -> repl
    s = shard_spec_for_path("embed/emb", (152064, 4096), MESH, cfg)
    assert tuple(s) == ("model", "data")
    s = shard_spec_for_path("blocks/ln1/g", (36, 4096), MESH, cfg)
    assert tuple(s) == ()


def test_rules_moe_ep_vs_expert_tp():
    qw = get_config("qwen3_moe_235b")               # 128 experts: EP
    s = shard_spec_for_path("blocks/moe/gate", (94, 128, 4096, 1536),
                            MESH, qw)
    assert tuple(s)[1] == "model"
    gk = get_config("grok1_314b")                   # 8 experts: expert-TP
    s = shard_spec_for_path("blocks/moe/gate", (64, 8, 6144, 32768),
                            MESH, gk)
    assert tuple(s)[-1] == "model" and "model" not in tuple(s)[:-1]


def test_gemma_attention_fully_replicated_across_tp():
    cfg = get_config("gemma3_1b")                   # 4 q heads, 1 kv head
    for path, shape in [("blocks/attn/q/w", (26, 1152, 1024)),
                        ("blocks/attn/k/w", (26, 1152, 256)),
                        ("blocks/attn/o/w", (26, 1024, 1152))]:
        s = shard_spec_for_path(path, shape, MESH, cfg)
        assert "model" not in tuple(s), (path, s)


@pytest.mark.slow
def test_mini_dryrun_16_devices(tmp_path):
    """Lower+compile a reduced train step on a (4,4) mesh in a subprocess;
    assert collectives exist and memory analysis is sane."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
        import json, sys
        import jax, jax.numpy as jnp
        sys.path.insert(0, "src")
        from repro.configs import get_config, Shape
        from repro.launch import steps
        from repro.launch.hlo_analysis import collective_bytes
        mesh = jax.make_mesh((4, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        cfg = get_config("qwen3_8b", reduced=True)
        shape = Shape("t", 128, 8, "train")
        with jax.set_mesh(mesh):
            jitted, args = steps.build_train_step(cfg, shape, mesh)
            compiled = jitted.lower(*args).compile()
        mem = compiled.memory_analysis()
        cb = collective_bytes(compiled.as_text())
        print(json.dumps({"temp": mem.temp_size_in_bytes,
                          "coll": cb["total"]}))
    """)
    p = subprocess.run([sys.executable, "-c", script], cwd="/root/repo",
                       capture_output=True, text=True, timeout=420)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["coll"] > 0, "sharded train step must contain collectives"
    assert 0 < out["temp"] < 16 * 2 ** 30


def test_hlo_collective_parser():
    txt = """
  %all-reduce.271 = f32[8,512]{1,0} all-reduce(%wrapped), channel_id=1
  %all-gather.5 = bf16[128,64]{1,0} all-gather(%p), replica_groups=[4,4]
  %meta = f32[2]{0} add(%a, %b), metadata={op_name="not all-reduce here"}
  %ar2 = (f32[4,4]{1,0}, f32[2,2]{1,0}) all-reduce-start(%x, %y)
"""
    from repro.launch.hlo_analysis import collective_bytes
    cb = collective_bytes(txt)
    assert cb["all-reduce"] == (8 * 512 * 4) * 2 + (16 * 4 + 4 * 4) * 2
    assert cb["all-gather"] == 128 * 64 * 2
    assert cb["total"] == cb["all-reduce"] + cb["all-gather"]
