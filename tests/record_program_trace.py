#!/usr/bin/env python3
"""Record the small chip trace that ``tests/test_tracing.py`` reads.

    python tests/record_program_trace.py [--out PATH]

on one TPU writes ``tests/data/program_trace.xplane.pb`` (or PATH):
whisper-tiny.en at full width with four lanes and random weights,
profiled through one frontend call, one admission (the prefill encodes
2 s of audio) and three decode ticks, with ``repro.tracing``'s spans in
the profile beside the device's programs. Exits 2 without a TPU.
"""

import argparse
import glob
import os
import shutil
import sys
import tempfile

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

OUT = os.path.join(HERE, "data", "program_trace.xplane.pb")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=OUT)
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("record_program_trace: needs a TPU", file=sys.stderr)
        return 2
    from repro import tracing
    from repro.audio.features import SAMPLE_RATE, audio_frames
    from repro.configs import get_config
    from repro.models.model import build
    from repro.serving.engine import AudioRequest, ServeEngine
    cfg = get_config("whisper-tiny-en")
    model = build(cfg)
    params = model.init_values(jax.random.key(1))
    engine = ServeEngine(model, params, n_slots=4, max_len=64,
                         enc_len=1500)
    t = np.arange(2 * SAMPLE_RATE) / SAMPLE_RATE
    wave = (0.1 * np.sin(2 * np.pi * 440.0 * t)).astype(np.float32)

    def once():
        fr = audio_frames(wave, cfg.d_model)
        engine.admit(AudioRequest(uid=0, tokens=[1, 2, 3], max_new=8,
                                  eos_id=-1, enc_frames=fr))
        for _ in range(3):
            engine.step_end(engine.step_begin())
        for st in list(engine.active.values()):
            engine.abort(st)

    once()                              # compile outside the profile
    tdir = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tdir, profiler_options=opts)
    once()
    jax.profiler.stop_trace()
    names = sorted({s.name for s in tracing.spans()})
    path = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    shutil.copy(path, args.out)
    shutil.rmtree(tdir)
    print(f"wrote {args.out} ({os.path.getsize(args.out)} bytes); "
          f"spans: {names}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
