"""Tiny configurations and tiny mixes for running the harness on the
CPU: the program's path and the benchmark's code at a size a test run
can hold (d_model 64, 2+2 layers, vocabulary 512, four lanes), for
Whisper and for the test-only dense family (``families/dense.py``)."""

import copy
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def _load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def config() -> dict:
    cfg = _load("configs", "whisper-tiny.en.json")
    cfg["name"] = "whisper-test"
    cfg["config"].update(d_model=64, decoder_attention_heads=2,
                         encoder_attention_heads=2, decoder_ffn_dim=128,
                         encoder_ffn_dim=128, decoder_layers=2,
                         encoder_layers=2, vocab_size=512)
    cfg["deployment"].update(n_slots=4, max_len=64, enc_len=150)
    cfg["prompt"] = {"sot_sequence": [500, 501], "startofprev": 502,
                     "text_tokens": 490}
    return cfg


def mix(name: str) -> dict:
    m = copy.deepcopy(_load("mixes", f"{name}.json"))
    if name == "backlog":
        m.update(window_s=3, prev_text_tokens=[0, 20], new_tokens=[4, 8],
                 lead_s=1.0, pool=512)
    elif name == "poisson":
        m.update(rate_per_s=4.0, seconds_grid=[1, 2, 3],
                 seconds_weights=[1, 1, 1], tokens_per_audio_s=2,
                 lead_s=0.5)
    elif name == "stream":
        m.update(rate_per_s=0.5, session_s=3, new_tokens=4, lead_s=1.0)
    return m


# ----------------------------------------------- a second, text family
DENSE = {
    "name": "dense-test",
    "source": "https://huggingface.co/Qwen/Qwen3-4B/blob/main/config.json",
    "family": "dense",
    "config": {"hidden_size": 64, "num_hidden_layers": 2,
               "num_attention_heads": 4, "num_key_value_heads": 2,
               "head_dim": 32, "intermediate_size": 256, "vocab_size": 512,
               "rope_theta": 1e6, "rms_norm_eps": 1e-6},
    "deployment": {"weights": "bf16", "cache_dtype": "bf16", "n_slots": 4,
                   "max_len": 64},
    "prompt": {"text_tokens": 500},
}
# chat: short prompts and answers, arriving in on/off bursts; the window
# (2 s in the tests) closes inside a burst, with lanes in flight
CHAT = {"kind": "open", "why": "bursty chat at a size a CPU test holds",
        "rate_per_s": 6.0, "burst_period_s": 1.0, "burst_duty": 0.5,
        "prompt_tokens": {"grid": [4, 12, 24], "weights": [2, 2, 1]},
        "new_tokens": {"grid": [8, 24], "weights": [1, 1]},
        "slo": "interactive", "lead_s": 0.25, "work_seed": 21,
        "fixed_order": True}
# self_kv_off: sound runs read 0.028-0.073 over ten seeds, the q8_0
# control 0.275-0.35 over four (on the CPU)
CHAT_LIMITS = {"requests": 12, "lanes": 2, "token_gap": 0.3,
               "self_kv_off": 0.15}


def dense_family():
    """The test-only family module, found by name under ``tests/``."""
    import spec
    return spec.family_module("dense", base=HERE)
