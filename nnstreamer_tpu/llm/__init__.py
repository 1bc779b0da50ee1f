"""Token-streaming LLM serving tier: session-keyed KV-cache pool +
continuous-batching decode plane.

The first STATEFUL workload the framework serves (ROADMAP item 5, the
sharpest test of the PR 9 cross-stream batcher): vLLM-style continuous
batching of token-streaming LLM inference, where variable-length
sequences join and leave the device bucket every decode step — the
inter-kernel streaming-dataflow framing of StreamTensor
(arXiv:2509.13694) applied to the decode loop, and the user-schedulable
non-MatMul-adjacent scheduling "Pushing Tensor Accelerators Beyond
MatMul" (arXiv:2512.02371) argues accelerators need.

Everything REUSES the existing serving plane rather than forking it:

- **pool.py** — :class:`KVCachePool`: fixed ``max_seq`` static-shape
  cache slots (the ``models/streamformer_lm.py`` decode contract)
  allocated per live stream; slot admission rides the PR 7
  :class:`~nnstreamer_tpu.query.overload.AdmissionController` (no free
  slot ⇒ explicit ``T_SHED`` with retry-after, never unbounded memory),
  LRU/deadline eviction on client disconnect or EOS.
- **engine.py** — :class:`DecodeEngine`: the continuous-batching decode
  core.  Each step gathers the per-session position indices and cache
  slot ids of every resident sequence and runs ONE padded
  ``decode_step_pooled`` invoke over the active set (the PR 9
  ``pad_rows`` quantization: a bounded set of warm executables serves
  every fill).  Tokens are sampled on the chip and stay there, so the
  element keeps one step in flight (``dispatch`` step k, then
  ``collect`` step k-1 and push its tokens while the chip works).
  Prefill routes through ``ops/flash_attention.py`` so
  long prompts never materialize (T, T) scores.  Exact, conserved
  prefill-vs-decode-vs-idle wall-time attribution.
- **element.py** — the stateful ``tensor_llm`` filter element: prompt
  request frames in, per-token ``[1, 1]`` reply frames out through
  ``tensor_query_serversink`` in exact per-client order, with the
  existing trace-context piggyback (one merged Chrome timeline shows
  prefill, per-step decode windows, and queue-wait per token).
- **paged.py** — :class:`PagedKVCachePool`: the block-paged arena
  (vLLM/PagedAttention layout) behind the same pool contract — memory
  proportional to what a session USES, content-hash prefix reuse
  (copy-on-write, refcounted), commitment-based page admission.
- **client.py** — :class:`TokenStreamClient`: the client half of the
  streaming reply contract over the unchanged query wire protocol.
"""

from .client import TokenStreamClient, TokenTimeoutError
from .engine import DecodeEngine, PhaseClock
from .paged import PagedKVCachePool
from .pool import KVCachePool, slot_admission_controller

__all__ = ["DecodeEngine", "KVCachePool", "PagedKVCachePool",
           "PhaseClock", "TokenStreamClient", "TokenTimeoutError",
           "slot_admission_controller"]
