"""NSA / FSA hyper-parameter bundle.

``NSAConfig`` carries the *algorithm* hyper-parameters of NSA (what is
computed); ``KernelPolicy`` carries the *implementation* bundle (which
registered ``repro.attention`` backend runs each mode, plus kernel tuning
knobs).  The two are deliberately separate: changing the policy must never
change the math.

Notation follows the paper (Table 1):
  N       sequence length
  d_K/d_V head dims (uniform d in practice)
  h       number of query heads
  h_K     number of KV heads,  g = h / h_K  (GQA group size)
  T       number of selected KV blocks per query token (``num_selected``)
  B_K     KV block size (``block_size``)
  B_Q     FSA query-batch (query-block) size (``q_block_size``)
"""
from __future__ import annotations

import dataclasses
import functools

import jax


@functools.cache
def platform_interpret() -> bool:
    """Whether Pallas kernels run in interpret mode, decided once per
    process from the platform: compiled on TPU, interpreted elsewhere (the
    CPU test runs).  Read lazily, so importing a config never initialises a
    JAX backend."""
    return jax.default_backend() != "tpu"


@dataclasses.dataclass(frozen=True)
class KernelPolicy:
    """Implementation bundle: which ``repro.attention`` backend runs each
    mode, plus kernel tuning knobs.  Swapping policies never changes the
    computed function — only how (and how fast) it is computed.

    ``"auto"`` defers the choice to ``repro.attention.resolve``, which picks
    the best *capable* backend for the request's shape/mode/platform.
    """

    backend: str = "auto"          # train/prefill backend (registry name)
    decode_backend: str = "auto"   # dense-cache decode backend
    paged_backend: str = "auto"    # paged-decode (serving) backend

    # --- kernel tuning knobs ---
    q_block_size: int = 128        # B_Q: query tokens per FSA batch (MXU M dim)
    # Pallas interpret mode; None = ``platform_interpret()`` (compiled on TPU)
    interpret: bool | None = None
    # slots folded per M block in the paged-decode kernel (0 = auto: fill the
    # MXU M dim to >= 8 rows)
    paged_slot_block: int = 0


# legacy selected_impl values -> registry backend names (public:
# repro.attention.api derives its legacy-alias table from this)
SELECTED_IMPL_TO_BACKEND = {"union": "sparse_union", "gather": "sparse_gather"}


@dataclasses.dataclass(frozen=True, init=False)
class NSAConfig:
    """Hyper-parameters of the NSA sparse-attention algorithm + the
    ``KernelPolicy`` implementation bundle (see module docstring)."""

    # --- NSA algorithm hyper-parameters (paper defaults: B_K=64, T=16) ---
    block_size: int = 64          # B_K: tokens per selected KV block
    num_selected: int = 16        # T: top-k selected blocks per query token
    cmp_block_size: int = 32      # l: compression block length
    cmp_stride: int = 16          # d: compression stride (overlapping blocks)
    window_size: int = 512        # sliding-window branch width
    num_init_blocks: int = 1      # forced-selected initial blocks
    num_local_blocks: int = 2     # forced-selected local (trailing) blocks

    # --- branch toggles (full-attention fallback for short sequences) ---
    min_seq_for_sparse: int = 256  # below this, dense attention is used

    # --- implementation bundle (backends + kernel knobs) ---
    policy: KernelPolicy = dataclasses.field(default_factory=KernelPolicy)

    def __init__(self, block_size: int = 64, num_selected: int = 16,
                 cmp_block_size: int = 32, cmp_stride: int = 16,
                 window_size: int = 512, num_init_blocks: int = 1,
                 num_local_blocks: int = 2, min_seq_for_sparse: int = 256,
                 policy: KernelPolicy | None = None,
                 # policy passthroughs (tuning knobs land on self.policy)
                 q_block_size: int | None = None, interpret: bool | None = None,
                 paged_slot_block: int | None = None):
        for name, val in (("block_size", block_size),
                          ("num_selected", num_selected),
                          ("cmp_block_size", cmp_block_size),
                          ("cmp_stride", cmp_stride),
                          ("window_size", window_size),
                          ("num_init_blocks", num_init_blocks),
                          ("num_local_blocks", num_local_blocks),
                          ("min_seq_for_sparse", min_seq_for_sparse)):
            object.__setattr__(self, name, val)

        policy = policy if policy is not None else KernelPolicy()
        over = {}
        if q_block_size is not None:
            over["q_block_size"] = q_block_size
        if interpret is not None:
            over["interpret"] = interpret
        if paged_slot_block is not None:
            over["paged_slot_block"] = paged_slot_block
        if over:
            policy = dataclasses.replace(policy, **over)
        object.__setattr__(self, "policy", policy)

    # ---------------------------------------------- policy view (no warning)
    # Tuning knobs read pervasively by the kernels; kept as plain forwarding
    # properties so call sites stay `cfg.q_block_size` / `cfg.interpret`.
    @property
    def q_block_size(self) -> int:
        return self.policy.q_block_size

    @property
    def interpret(self) -> bool:
        if self.policy.interpret is None:
            return platform_interpret()
        return self.policy.interpret

    @property
    def paged_slot_block(self) -> int:
        return self.policy.paged_slot_block

    # ------------------------------------------------------------- derived
    def num_kv_blocks(self, seq_len: int) -> int:
        return max(1, (seq_len + self.block_size - 1) // self.block_size)

    def num_cmp_blocks(self, seq_len: int) -> int:
        if seq_len < self.cmp_block_size:
            return 1
        return (seq_len - self.cmp_block_size) // self.cmp_stride + 1

    def effective_T(self, seq_len: int) -> int:
        """T clamped to the number of KV blocks (short sequences)."""
        return min(self.num_selected, self.num_kv_blocks(seq_len))

    def validate(self) -> None:
        assert self.block_size % 8 == 0, "B_K must be TPU-sublane aligned"
        assert self.q_block_size % 8 == 0, "B_Q must be TPU-sublane aligned"
        assert self.cmp_block_size % self.cmp_stride == 0
        assert self.num_init_blocks >= 1 and self.num_local_blocks >= 1
