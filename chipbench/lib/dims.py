"""A configuration file's model sizes, under the short names the weight
maker, the plain reference and the FLOP/byte functions use."""
from __future__ import annotations

import dataclasses

# configuration-file activation name → (reference function name, the
# program's ModelConfig.act it must run as)
ACTS = {"relu": "relu", "gelu_pytorch_tanh": "gelu"}


@dataclasses.dataclass(frozen=True)
class Dims:
    L: int          # layers
    D: int          # hidden size
    H: int          # query heads
    Hkv: int        # key/value heads
    hd: int         # head size
    F: int          # MLP inner size
    V: int          # vocabulary
    tied: bool      # output head = embedding
    theta: float    # RoPE base
    eps: float      # LayerNorm epsilon
    act: str        # "relu" | "gelu" (tanh approximation)

    def linears(self):
        """(name, d_out, d_in) of each weight matmul of one layer."""
        Hq, Hk = self.H * self.hd, self.Hkv * self.hd
        return (("mix.wq", Hq, self.D), ("mix.wk", Hk, self.D),
                ("mix.wv", Hk, self.D), ("mix.wo", self.D, Hq),
                ("mlp.w1", self.F, self.D), ("mlp.w2", self.D, self.F))


def dims(conf: dict) -> Dims:
    return Dims(L=int(conf["num_hidden_layers"]), D=int(conf["hidden_size"]),
                H=int(conf["num_attention_heads"]),
                Hkv=int(conf["num_key_value_heads"]),
                hd=int(conf["head_dim"]), F=int(conf["intermediate_size"]),
                V=int(conf["vocab_size"]),
                tied=bool(conf["tie_word_embeddings"]),
                theta=float(conf["rope_theta"]),
                eps=float(conf["norm_epsilon"]), act=ACTS[conf["hidden_act"]])
