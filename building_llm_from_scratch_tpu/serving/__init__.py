"""Serving subsystem: continuous-batching decode engine.

The reference stops at one-shot batch sampling (generate.py:4-75); this
package is the runtime that turns the repo's decode primitives (static
KV cache, slot-batched decode step) into a server: a bounded ``RequestQueue``,
an FCFS slot ``Scheduler``, the ``DecodeEngine`` tick loop, and two
dependency-free frontends (JSONL batch, stdlib HTTP).

    from building_llm_from_scratch_tpu.serving import (
        DecodeEngine, SamplingParams)
    engine = DecodeEngine(cfg, params, tokenizer, n_slots=8)
    engine.warmup(); engine.start()
    req = engine.submit("Every effort moves you",
                        SamplingParams(max_new_tokens=64, seed=7))
    for piece in req.stream():
        print(piece, end="")
    engine.shutdown()

CLI: ``python -m building_llm_from_scratch_tpu --mode serve ...`` (or the
installed ``bllm-tpu`` entry point) — see README "Serving".
"""

from building_llm_from_scratch_tpu.serving.adapters import (
    AdapterMismatchError,
    AdapterRegistry,
    AdapterRegistryFullError,
)
from building_llm_from_scratch_tpu.serving.engine import DecodeEngine
from building_llm_from_scratch_tpu.serving.fleet import (
    ProcessFleet,
    WorkerSupervisor,
)
from building_llm_from_scratch_tpu.serving.kvcache import (
    KVCachePolicy,
    PrefixStore,
)
from building_llm_from_scratch_tpu.serving.queue import (
    EngineDrainingError,
    PromptTooLongError,
    QueueFullError,
    RequestQueue,
    SLOShedError,
)
from building_llm_from_scratch_tpu.serving.request import (
    Request,
    RequestExpiredError,
    SamplingParams,
)
from building_llm_from_scratch_tpu.serving.router import EngineRouter
from building_llm_from_scratch_tpu.serving.scheduler import Scheduler
from building_llm_from_scratch_tpu.serving.spec import (
    Drafter,
    NgramDrafter,
)
from building_llm_from_scratch_tpu.serving.supervisor import (
    EngineSupervisor,
    FaultHooks,
)
from building_llm_from_scratch_tpu.serving.transport import (
    FrameCorruptError,
    FrameTooLargeError,
    PeerGoneError,
    PeerTimeoutError,
    TransportError,
)
from building_llm_from_scratch_tpu.serving.worker import (
    EngineSpec,
    FakeEngine,
)

__all__ = [
    "AdapterMismatchError",
    "AdapterRegistry",
    "AdapterRegistryFullError",
    "DecodeEngine",
    "Drafter",
    "EngineDrainingError",
    "EngineRouter",
    "EngineSpec",
    "EngineSupervisor",
    "FakeEngine",
    "FaultHooks",
    "FrameCorruptError",
    "FrameTooLargeError",
    "KVCachePolicy",
    "NgramDrafter",
    "PeerGoneError",
    "PeerTimeoutError",
    "PrefixStore",
    "ProcessFleet",
    "PromptTooLongError",
    "QueueFullError",
    "Request",
    "RequestExpiredError",
    "RequestQueue",
    "SLOShedError",
    "SamplingParams",
    "Scheduler",
    "TransportError",
    "WorkerSupervisor",
]
