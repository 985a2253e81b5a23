"""The benchmark's own view of a model configuration file: the sizes the
reference, the work counts and the traffic need, read from the published
keys (``hidden_size``, ``num_attention_heads``, ...)."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Spec:
    """One dense decoder configuration as it is run."""

    name: str
    family: str
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm_eps: float
    qk_norm: bool
    tie_embeddings: bool

    @property
    def layer_params(self) -> int:
        """Matmul weights of one layer (q, k, v, o, gate, up, down)."""
        d, hd = self.d_model, self.head_dim
        attn = d * hd * (self.heads + 2 * self.kv_heads) + self.heads * hd * d
        return attn + 3 * d * self.d_ff

    @property
    def kv_bytes_per_token_layer(self) -> int:
        """bf16 K and V of one token in one layer."""
        return 2 * self.kv_heads * self.head_dim * 2


def spec_from_file(cfg: dict) -> Spec:
    """Read a configuration file's published keys."""
    if cfg.get("hidden_act") != "silu":
        raise ValueError(f"{cfg['name']}: only SwiGLU (hidden_act silu) dense layers are supported")
    return Spec(
        name=cfg["name"],
        family=cfg["family"],
        layers=int(cfg["num_hidden_layers"]),
        d_model=int(cfg["hidden_size"]),
        heads=int(cfg["num_attention_heads"]),
        kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]),
        d_ff=int(cfg["intermediate_size"]),
        vocab=int(cfg["vocab_size"]),
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        qk_norm=bool(cfg.get("qk_norm", False)),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
    )
