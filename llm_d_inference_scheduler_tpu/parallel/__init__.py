from .mesh import make_mesh, mesh_shape
from .shardings import param_pspecs, ACT_SPEC
from .ring_attention import ring_attention, make_ring_attention_fn
from .train import make_train_state, make_train_step
from .serve import (
    make_serve_mesh,
    serve_shardings,
    init_sharded_params,
    dryrun_serve,
)
from .pipeline import (
    make_pp_mesh,
    make_pp_forward,
    shard_params_pp,
    dryrun_pipeline,
)

__all__ = [
    "make_mesh",
    "mesh_shape",
    "param_pspecs",
    "ACT_SPEC",
    "ring_attention",
    "make_ring_attention_fn",
    "make_train_state",
    "make_train_step",
    "make_serve_mesh",
    "serve_shardings",
    "init_sharded_params",
    "dryrun_serve",
    "make_pp_mesh",
    "make_pp_forward",
    "shard_params_pp",
    "dryrun_pipeline",
]
