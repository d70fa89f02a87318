"""bigdl_tpu.parallel — distributed engine (reference: parameters/ +
optim/DistriOptimizer + utils/Engine, SURVEY.md §2.5): device mesh discovery,
flat-parameter collectives over ICI, and the SPMD training loop."""

from bigdl_tpu.parallel.engine import Engine, EngineType
from bigdl_tpu.parallel.all_reduce import (
    AllReduceParameter, flatten_params, unflatten_params, pad_to_multiple,
    compress, decompress,
)
from bigdl_tpu.parallel.distri_optimizer import DistriOptimizer
from bigdl_tpu.parallel.ring_attention import ring_attention, ulysses_attention
from bigdl_tpu.parallel.tp import (
    fetch_to_host, kv_page_pool_sharding, kv_page_pool_spec,
    kv_pool_sharding, kv_pool_spec, put_from_host, replicate,
    spec_for_params, transformer_tp_rules, shard_params,
)
from bigdl_tpu.parallel.pipeline import pipeline_spmd, stack_stage_params
from bigdl_tpu.parallel.moe import MoEMLP, moe_spmd
