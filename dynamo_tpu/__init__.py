"""dynamo-tpu: a TPU-native distributed LLM inference serving framework.

A ground-up rebuild of the capabilities of NVIDIA Dynamo (reference:
/root/reference) designed for TPU hardware: the compute path is JAX/XLA/Pallas
over `jax.sharding.Mesh`, the serving runtime is asyncio + a built-in TCP
control/request plane, and KV movement rides XLA collectives / host DMA
instead of NIXL.

Layer map (mirrors reference SURVEY.md §1):
  runtime/   — distributed runtime: discovery, component model, request plane
  llm/       — serving pipeline: protocols, preprocessor, HTTP frontend,
               KV router, block manager, mocker engine
  engine/    — the JAX inference engine: continuous batching, paged KV
  models/    — model zoo (functional JAX, param pytrees)
  ops/       — Pallas TPU kernels. Kernel map (each with an XLA reference
               fallback + the shared `_pallas_eligible` dispatch gate in
               ops/paged_attention.py):
                 pallas_paged_attention.py   — decode (T=1) flash over paged
                                               KV, + fused pool+local variant
                 pallas_prefill_attention.py — batched chunked-prefill flash
                 pallas_ragged_attention.py  — ragged UNIFIED mixed
                                               prefill+decode (one flat
                                               buffer, one dispatch;
                                               docs/ragged_attention.md)
                 pallas_delta_step.py        — the hybrid family's decode
                                               recurrence, a lane's state
                                               read and written once in
                                               place (docs/hybrid_models.md)
                 ring_attention.py           — sequence-parallel ring prefill
  parallel/  — mesh construction, shardings (tp/dp/pp/ep/sp)
  planner/   — SLA planner: load prediction, perf interpolation, autoscale
  frontend/  — `python -m dynamo_tpu.frontend` OpenAI entrypoint
"""

__version__ = "0.1.0"
