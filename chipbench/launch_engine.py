"""Start the program's engine server on a benchmark configuration.

    python chipbench/launch_engine.py --config chipbench/configs/<name>.json \
        --weights-seed N [--trace-dir DIR] -- <engine.server arguments>

The engine CLI can serve only models its registry names, takes no seed and
has no profiler hook, and the benchmark may not edit the program. So this
launcher, in the engine's own process and before the CLI runs:

- maps the configuration file's published keys to a `ModelConfig` with the
  program's own `config_from_hf`, and registers it under the name served;
- gives `EngineConfig` the weights' seed (the CLI builds it without one);
- with --trace-dir, starts a thread that starts `jax.profiler` when the file
  `<dir>/start` appears and stops it when `<dir>/stop` does, then writes
  `<dir>/done`: only the process that holds the chip can trace it.

Then it calls `engine.server.main(argv)`, the normal entry point: device
narrowing, the compile cache, the refusal of an unasked CPU and the serving
loop are all as a user gets them.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import threading
import time
import types

# Keys of a published config.json that say nothing the model code reads.
_NOT_MODEL_KEYS = ("source", "reduced", "assumed", "departures", "deployment",
                   "serve", "reference")


def model_config_from_file(path: str):
    """The program's ModelConfig for a configuration file, by the program's
    own mapping of published keys."""
    from llm_d_inference_scheduler_tpu.models.convert_hf import config_from_hf

    with open(path) as f:
        doc = json.load(f)
    published = {k: v for k, v in doc.items() if k not in _NOT_MODEL_KEYS}
    return config_from_hf(types.SimpleNamespace(**published),
                          name=doc["serve"]["model_name"])


def _trace_on_request(trace_dir: str) -> None:
    """Runs in a daemon thread of the engine process."""
    import jax

    start, stop = (os.path.join(trace_dir, n) for n in ("start", "stop"))
    while not os.path.exists(start):
        time.sleep(0.02)
    options = jax.profiler.ProfileOptions()
    # Device and runtime events only: tracing every Python call would slow
    # the engine's host loop, which is part of what is measured.
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    t_start = time.time()
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    t0 = time.time()
    while not os.path.exists(stop):
        time.sleep(0.02)
    t1 = time.time()
    jax.profiler.stop_trace()
    with open(os.path.join(trace_dir, "done"), "w") as f:
        json.dump({"traced_s": t1 - t0, "start_trace_s": t0 - t_start,
                   "stop_trace_s": time.time() - t1}, f)


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--weights-seed", type=int, default=0)
    ap.add_argument("--trace-dir", default=None)
    ap.add_argument("engine_argv", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    engine_argv = [a for a in args.engine_argv if a != "--"]

    from llm_d_inference_scheduler_tpu.engine import server
    from llm_d_inference_scheduler_tpu.models import configs

    mcfg = model_config_from_file(args.config)
    configs._REGISTRY[mcfg.name] = mcfg
    # jax.random.key takes a seed below 2**32; the driver's seeds can exceed
    # 32 signed bits.
    server.EngineConfig = functools.partial(
        server.EngineConfig, seed=args.weights_seed % (2 ** 31))
    if args.trace_dir:
        os.makedirs(args.trace_dir, exist_ok=True)
        threading.Thread(target=_trace_on_request, args=(args.trace_dir,),
                         daemon=True).start()
    server.main(engine_argv)


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    main()
