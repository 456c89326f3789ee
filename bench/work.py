"""The work the algorithm needs, counted from the configuration and the
tokens each row actually holds, never from what a program or kernel
happens to touch. A kernel that walks every table slot therefore keeps a
low roofline share; one that reads only held tokens raises it.

Dense decoder, per layer and per query position at context ``ctx``
(tokens attended, the query's own included):

- attention in the paged kernel: ``4 * ctx * Hq * hd`` FLOPs (q.k and
  p.v, two per multiply-add);
- kernel bytes per row per call: K and V of the tokens the row holds,
  ``2 * ctx_max * Hkv * hd`` elements, plus q and out of its queries,
  ``2 * S * Hq * hd`` elements, all in the served dtype (bf16, 2 bytes);
- model FLOPs per query token: ``2 *`` the matmul parameters it passes
  through (q, kv, o projections and the gated MLP) plus the attention
  above; a position whose logits become a token also pays the head,
  ``2 * d * V``.
"""
from __future__ import annotations

from dataclasses import dataclass, field

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


@dataclass(frozen=True)
class Dense:
    """The sizes of a dense decoder that the counts need."""
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    dtype_bytes: int = 2

    @classmethod
    def from_config(cls, c: dict) -> "Dense":
        return cls(n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   n_heads=c["num_attention_heads"],
                   n_kv_heads=c["num_key_value_heads"],
                   head_dim=c.get("head_dim") or
                   c["hidden_size"] // c["num_attention_heads"],
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                   dtype_bytes=DTYPE_BYTES[c["torch_dtype"]])

    @property
    def layer_matmul_params(self) -> int:
        d, hd = self.d_model, self.head_dim
        attn = d * self.n_heads * hd * 2 + d * 2 * self.n_kv_heads * hd
        return attn + 3 * d * self.d_ff

    def attn_flops(self, ctx: int) -> int:
        """Paged-kernel FLOPs of one query position, all layers."""
        return 4 * ctx * self.n_heads * self.head_dim * self.n_layers

    def kernel_bytes(self, ctx_max: int, queries: int) -> int:
        """Bytes one row's kernel call must move, all layers."""
        kv = 2 * ctx_max * self.n_kv_heads * self.head_dim
        qo = 2 * queries * self.n_heads * self.head_dim
        return (kv + qo) * self.dtype_bytes * self.n_layers

    def token_flops(self, ctx: int, logits: bool) -> int:
        """Model FLOPs of one query position, all layers (head included
        where the position's logits become a token)."""
        f = 2 * self.layer_matmul_params * self.n_layers + self.attn_flops(ctx)
        return f + (2 * self.d_model * self.vocab if logits else 0)


@dataclass
class Tally:
    """Work done by one program over a window."""
    queries: int = 0
    attn_flops: int = 0
    kernel_bytes: int = 0
    model_flops: int = 0
    calls: int = 0

    def add_row(self, m: Dense, ctxs: list, logits: int, *,
                kernel: bool = True) -> None:
        """One row of one call: query positions attending ``ctxs`` tokens,
        ``logits`` of which produce a token."""
        if not ctxs:
            return
        self.queries += len(ctxs)
        self.model_flops += sum(m.token_flops(c, False) for c in ctxs) \
            + logits * 2 * m.d_model * m.vocab
        if kernel:
            self.attn_flops += sum(m.attn_flops(c) for c in ctxs)
            self.kernel_bytes += m.kernel_bytes(max(ctxs), len(ctxs))


@dataclass
class Work:
    """Per-program tallies of a window: ``admit``, ``decode``, ``chunk``."""
    programs: dict = field(default_factory=lambda: {
        "admit": Tally(), "decode": Tally(), "chunk": Tally()})

    def __getitem__(self, name: str) -> Tally:
        return self.programs[name]


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peaks: dict) -> tuple:
    """(share of the roofline in %, the bound: ``compute`` or
    ``memory``): the least time the chip could take, the larger of
    FLOPs over peak FLOP/s and bytes over peak bytes/s, over the time
    taken."""
    t_flops = flops / peaks["bf16_flops"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    bound = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
