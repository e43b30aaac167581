#!/usr/bin/env python3
"""Record the small chip trace the trace-reduction tests read.

    python bench/tests/record_trace.py

on one TPU writes ``bench/tests/data/chip_trace.xplane.pb``: whisper-tiny.en
at full width with four lanes, traced through one frontend call, one
admission (prefill with the encoder over 2 s of audio), a 20 ms host
pause, and three decode ticks, each inside the benchmark's own spans.
"""

import glob
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

OUT = os.path.join(HERE, "data", "chip_trace.xplane.pb")


def main() -> int:
    import jax
    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs the chip", file=sys.stderr)
        return 2
    import model as bench_model
    import serve
    import spec
    import trace_reduce
    from repro.audio.features import audio_frames
    from repro.serving.engine import AudioRequest, ServeEngine
    cfg = spec.load_config(spec.load_benchmark(), "whisper-tiny.en")
    family = spec.family_module(cfg["family"])
    model = serve._build(family.arch_config(cfg))
    params = bench_model.make_weights(model, 1, family.init_leaf)
    engine = ServeEngine(model, params, n_slots=4, max_len=64,
                         enc_len=1500)
    spans = serve.Spans()
    serve.instrument(engine, spans)
    wave = family.waveform(2.0, 1)
    prompt = list(cfg["prompt"]["sot_sequence"])

    def once():
        with spans.span("frontend"):
            fr = audio_frames(wave, cfg["config"]["d_model"])
            fr.block_until_ready()
        engine.admit(AudioRequest(uid=0, tokens=prompt, max_new=8,
                                  eos_id=-1, enc_frames=fr))
        with spans.span("pause"):
            time.sleep(0.02)
        for _ in range(3):
            engine.step_end(engine.step_begin())
        for st in list(engine.active.values()):
            engine.abort(st)

    once()                              # compile outside the trace
    tdir = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tdir, profiler_options=opts)
    spans.tracing = True
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        once()
    spans.tracing = False
    jax.profiler.stop_trace()
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    shutil.copy(trace_reduce.find_xplane(tdir), OUT)
    shutil.rmtree(tdir)
    print(f"wrote {OUT} ({os.path.getsize(OUT)} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
