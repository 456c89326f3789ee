"""The bytes a hybrid (Mamba-2 + attention) decoder's decode program
must move, counted from the configuration and the harness's own records
of the rows each call stepped, never from what the program happens to
touch:

- every weight once per call (a decode step reads each layer's matrices
  and the tied head whole), in the served dtype;
- each stepped row's recurrent state, read and written: per Mamba-2
  layer the float32 SSM state (heads x head dim x state) and the conv
  state (``d_conv - 1`` inputs of the conv's width, served dtype);
- the attention layers' kernel bytes as ``bench/work.py`` counts them
  (K and V of the tokens a row holds, q and out of its query), for the
  attention layers only.

A floor: a program that reads all rows' state, or pads what it reads,
moves more, never less.
"""
from __future__ import annotations

from dataclasses import dataclass

from bench.work import DTYPE_BYTES, Tally

SSM_STATE_BYTES = 4          # float32


@dataclass(frozen=True)
class Hybrid:
    weight_bytes: int
    state_bytes_per_row: int
    n_attn: int
    n_layers: int

    @classmethod
    def from_config(cls, c: dict) -> "Hybrid":
        b = DTYPE_BYTES[c["torch_dtype"]]
        d, F = c["hidden_size"], c["shared_intermediate_size"]
        Hq, Hkv = c["num_attention_heads"], c["num_key_value_heads"]
        hd = d // Hq
        H, P, N = c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"]
        G, K = c["mamba_n_groups"], c["mamba_d_conv"]
        di = H * P
        conv = di + 2 * G * N
        types = c["layer_types"]
        n_m, n_a = types.count("mamba"), types.count("attention")
        mamba = d * (di + conv + H) + K * conv + conv + 3 * H + di + di * d
        attn = d * Hq * hd + d * 2 * Hkv * hd + Hq * hd * d
        layer = 2 * d + 3 * d * F
        vocab = -(-c["vocab_size"] // 128) * 128
        head = 0 if c["tie_word_embeddings"] else d * vocab
        params = vocab * d + head + d + n_m * mamba + n_a * attn \
            + len(types) * layer
        state = n_m * (H * P * N * SSM_STATE_BYTES + (K - 1) * conv * b)
        return cls(weight_bytes=params * b, state_bytes_per_row=state,
                   n_attn=n_a, n_layers=len(types))

    def decode_bytes(self, tally: Tally) -> int:
        """Bytes the decode program's calls of a window must move: the
        weights per call, the state of each row a call stepped (one
        query per row in a decode call), and the attention layers' part
        of the tally's kernel bytes (counted there for every layer)."""
        return (self.weight_bytes * tally.calls
                + 2 * self.state_bytes_per_row * tally.queries
                + tally.kernel_bytes * self.n_attn // self.n_layers)
