"""Start the program's engine server on a benchmark configuration.

    python chipbench/launch_engine.py --config chipbench/configs/<name>.json \
        --weights-seed N -- <engine.server arguments>

The engine CLI can serve only models its registry names and takes no seed.
So this launcher, in the engine's own process and before the CLI runs:

- maps the configuration file's published keys to a `ModelConfig` with the
  program's own `config_from_hf`, and registers it under the name served;
- gives `EngineConfig` the weights' seed (the CLI builds it without one).

Then it calls `engine.server.main(argv)`, the normal entry point: device
narrowing, the compile cache, the refusal of an unasked CPU, the serving
loop and the profiler's control (`--profile-dir`, which run.py passes for a
traced run) are all as a user gets them.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
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


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--weights-seed", type=int, default=0)
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
    server.main(engine_argv)


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    main()
