"""deepspeed_tpu: a TPU-native training & inference framework with the
capabilities of DeepSpeed (reference: zhengchenyu/DeepSpeed v0.18.3), rebuilt
idiomatically on JAX/XLA/pjit/Pallas.

Public API mirrors the reference ``deepspeed/__init__.py``:
``initialize`` (:78), ``init_inference`` (:302), ``init_distributed``,
``add_config_arguments`` (:279), ``zero``, ``comm``.
"""

import time

_T_IMPORT0 = time.monotonic()  # ``setup.import``: this file's first line to its last

from typing import Any, Callable, Optional, Union

from deepspeed_tpu.version import __version__
from deepspeed_tpu import comm
from deepspeed_tpu.runtime import zero
from deepspeed_tpu.accelerator import get_accelerator
from deepspeed_tpu.accelerator.device import setup_compile_cache
from deepspeed_tpu.comm.comm import init_distributed
from deepspeed_tpu.parallel.topology import Topology, get_topology, set_topology
from deepspeed_tpu.runtime.config import DeepSpeedConfig
from deepspeed_tpu.runtime.engine import DeepSpeedEngine
from deepspeed_tpu.observability.setup_record import (
    get_setup_record,
    install_compile_listeners,
    setup_span,
)
from deepspeed_tpu.utils.logging import log_dist, logger


@setup_span("setup.initialize")
def initialize(
    args=None,
    model: Optional[Callable] = None,
    optimizer=None,
    model_parameters: Any = None,
    training_data=None,
    lr_scheduler=None,
    distributed_port: int = 29500,
    mpu=None,
    dist_init_required: Optional[bool] = None,
    collate_fn=None,
    config: Union[str, dict, None] = None,
    mesh_param=None,
    config_params=None,
    param_specs=None,
):
    """Create a training engine (reference ``deepspeed.initialize``
    __init__.py:78). Returns ``(engine, optimizer, dataloader, lr_scheduler)``.

    TPU adaptation: ``model`` is a pure loss function
    ``loss_fn(params, batch[, rng]) -> loss | (loss, aux)`` and
    ``model_parameters`` is the params pytree — or a ``zero.Init``/callable
    for deferred construction (params materialize under jit with the ZeRO
    plan's shardings; the full pytree never exists on one host). A flax
    ``nn.Module`` can be adapted via ``deepspeed_tpu.models.flax_loss_fn``. ``mesh_param`` (the
    reference's DeviceMesh knob, __init__.py:163-171) or the config's
    ``mesh`` section sizes the parallelism grid.
    """
    log_dist(f"DeepSpeedTPU info: version={__version__}", ranks=[0])
    setup_compile_cache()
    if model is None:
        raise ValueError("deepspeed_tpu.initialize: model (loss function) is required")
    if model_parameters is None:
        raise ValueError("deepspeed_tpu.initialize: model_parameters (params pytree) is required")

    config = config if config is not None else config_params
    if config is None and args is not None and hasattr(args, "deepspeed_config") and args.deepspeed_config:
        config = args.deepspeed_config

    # 1. mesh/topology (reference: comm.init_distributed + groups from mpu)
    mesh_cfg = None
    if mesh_param is not None:
        mesh_cfg = (
            {"data": mesh_param[0], "sequence": mesh_param[1]}
            if isinstance(mesh_param, (tuple, list))
            else dict(mesh_param)
        )
    # parse once (with duplicate-key rejection) so mesh extraction and the
    # typed config read the same dict
    if isinstance(config, str):
        import json

        from deepspeed_tpu.runtime.config_utils import dict_raise_error_on_duplicate_keys

        with open(config) as f:
            config = json.load(f, object_pairs_hook=dict_raise_error_on_duplicate_keys)
    raw = config if isinstance(config, dict) else {}

    if mpu is not None and not isinstance(mpu, Topology):
        logger.warning(
            f"mpu of type {type(mpu).__name__} is not a Topology and will be ignored; "
            "pass a deepspeed_tpu.Topology to control the mesh"
        )
        mpu = None
    if mpu is not None:
        # still bootstrap multi-host jax.distributed before adopting the mesh
        init_distributed(distributed_port=distributed_port)
        topo = mpu
        set_topology(topo)
    else:
        mc = dict(raw.get("mesh", {}) or {})
        if mesh_cfg:
            mc.update(mesh_cfg)
        tp = raw.get("tensor_parallel", {}).get("autotp_size", 0) or raw.get("tensor_parallel", {}).get("tp_size", 1)
        if tp and tp > 1 and "model" not in mc:
            mc["model"] = tp
        pp = raw.get("pipeline", {}).get("stages", 1)
        if pp > 1 and "pipe" not in mc:
            mc["pipe"] = pp
        # MiCS/hpZ shard-group axis: factorize data parallelism into
        # (data=groups, zero=shard-group) so ZeRO can partition within a group
        zc = raw.get("zero_optimization", {}) or {}
        mics = int(zc.get("mics_shard_size", -1) or -1)
        hpz = int(zc.get("zero_hpz_partition_size", 1) or 1)
        if mics > 0 and hpz > 1 and mics != hpz:
            raise ValueError(
                f"mics_shard_size={mics} and zero_hpz_partition_size={hpz} conflict: "
                "they would need different shard-group sizes — configure one"
            )
        shard = mics if mics > 0 else hpz
        if shard > 1:
            if "zero" in mc and mc["zero"] != shard:
                raise ValueError(
                    f"mesh zero={mc['zero']} does not match the configured "
                    f"shard-group size {shard}"
                )
            mc["zero"] = shard
            if mc.get("data"):
                if mc["data"] % shard:
                    raise ValueError(
                        f"mesh data={mc['data']} not divisible by shard-group size {shard}"
                    )
                mc["data"] = mc["data"] // shard
        init_distributed(distributed_port=distributed_port, mesh_config=mc or None)
        topo = get_topology()

    # 2. typed config with batch arithmetic against the real dp world
    ds_config = DeepSpeedConfig.load(raw, dp_world_size=topo.dp_world_size)

    # 3. engine
    engine = DeepSpeedEngine(
        loss_fn=model,
        params=model_parameters,
        config=ds_config,
        topology=topo,
        optimizer=optimizer,
        lr_scheduler=lr_scheduler,
        training_data=training_data,
        collate_fn=collate_fn,
        param_specs=param_specs,
    )

    # hybrid engine (reference __init__.py:190): train↔generate on one copy
    hy = raw.get("hybrid_engine", {}) or {}
    if hy.get("enabled"):
        model_config = getattr(model, "model_config", None)
        if model_config is None:
            raise ValueError(
                "hybrid_engine requires a model with a known architecture: use "
                "make_loss_fn(config) (which carries .model_config) or build "
                "DeepSpeedHybridEngine directly with your TransformerConfig"
            )
        from deepspeed_tpu.runtime.hybrid_engine import DeepSpeedHybridEngine

        engine = DeepSpeedHybridEngine(engine, model_config, hy)
        return engine, engine.engine.optimizer, engine.engine.training_dataloader, engine.engine.lr_scheduler
    return engine, engine.optimizer, engine.training_dataloader, engine.lr_scheduler


def init_inference(model=None, config=None, **kwargs):
    """Reference ``init_inference`` (__init__.py:302)."""
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine

    if isinstance(config, dict):
        config = DeepSpeedInferenceConfig.from_dict(config)
    elif config is None:
        config = DeepSpeedInferenceConfig.from_dict(kwargs)
    return InferenceEngine(model, config)


def add_config_arguments(parser):
    """Reference ``add_config_arguments`` (__init__.py:279)."""
    group = parser.add_argument_group("DeepSpeed", "DeepSpeed configurations")
    group.add_argument("--deepspeed", default=False, action="store_true")
    group.add_argument("--deepspeed_config", default=None, type=str)
    group.add_argument("--deepscale", default=False, action="store_true")
    group.add_argument("--deepscale_config", default=None, type=str)
    return parser


def _add_core_arguments(parser):
    from deepspeed_tpu.runtime.lr_schedules import add_tuning_arguments

    parser = add_config_arguments(parser)
    parser = add_tuning_arguments(parser)
    return parser


# every compile of the process from here on is a span of the set-up record
install_compile_listeners()
get_setup_record().add("setup.import", _T_IMPORT0)
