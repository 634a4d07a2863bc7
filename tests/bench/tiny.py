"""Tiny stand-ins for the benchmark's configurations and mixes, so the
harness, its reference and its faults run on the CPU in a test: vit-base
for the federated rounds, qwen2.5-14b's family (GQA, QKV bias, SwiGLU,
RMSNorm, untied output layer) for split serving."""
import copy
import time

from bench import common

VIT = copy.deepcopy(common.load_json(common.BENCH / "configs"
                                     / "vit-base.json"))
VIT.update(hidden_size=32, num_hidden_layers=3, num_attention_heads=2,
           num_key_value_heads=2, head_dim=16, intermediate_size=64,
           image_size=8, patch_size=4, num_labels=10)
VIT["split"] = dict(VIT["split"], body_layers=1, prompt_len=2)

ROUNDS = {"clients": 4, "samples_per_client": 8, "client_sizes": [8],
          "cohort": 2, "batch": 4, "local_epochs": 2, "secure": False,
          "check_rounds": 2}


def rounds(cell: str):
    """The tiny mix with the cell's own aggregation."""
    mix = common.load_json(common.BENCH / "traffic" / (
        common.resolve(cell)["cell"]["traffic"] + ".json"))
    keys = ("frac_bits", "mask_seed") if mix["secure"] else ()
    return dict(ROUNDS, secure=mix["secure"], **{k: mix[k] for k in keys})


def limits(cell: str):
    return common.load_json(common.BENCH / "limits" / f"{cell}.json")


def run(config, traffic, ref_name, cell, *, seed=7, seconds=0.6,
        plant=None, trace=False):
    import jax
    r = common.Run(config=config, traffic=traffic,
                   ref=common.load_module(common.BENCH / "configs"
                                          / f"{ref_name}.py"),
                   limits=limits(cell), seed=seed, seconds=seconds,
                   trace=trace, devices=jax.devices()[:1],
                   clock=common.CompileClock(), t_start=time.time(),
                   plant=plant)
    system = common.load_module(common.BENCH / "systems"
                                / f"{config['system']}.py")
    return system.run(r)


def correct(out) -> bool:
    return all(c["value"] <= c["limit"] for c in out["checks"])

QWEN = copy.deepcopy(common.load_json(common.BENCH / "configs"
                                      / "qwen2.5-14b.json"))
# rope_theta 1e4: at 8 frequencies and 32 positions, 1e6 would turn a
# position off by one into a rotation too small to see
QWEN.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
            head_dim=16, intermediate_size=128, vocab_size=256,
            rope_theta=10000.0)
QWEN["serve"] = dict(QWEN["serve"], max_seq=32, page_size=8, decode_block=4)

CLOSED = dict(common.load_json(common.BENCH / "traffic"
                               / "q14b-decode-closed.json"),
              prompt_lengths=[4, 8], output_lengths=[3, 6], margin_sample=3,
              drain_seconds=30)
