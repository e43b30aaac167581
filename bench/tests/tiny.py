"""A tiny configuration and tiny mixes for running the harness on the
CPU: the program's path and the benchmark's code at a size a test run
can hold (d_model 64, 2+2 layers, vocabulary 512, four lanes)."""

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
