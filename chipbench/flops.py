"""Forward operations the model needs for the tokens it serves, from the
configuration file's sizes (2 operations per multiply-add).

Counted: every matrix product of a layer, attention over each token's
causal context (``q k`` and ``p v``), and the LM head once per served token
(the first from the prompt's last position, the rest from decode steps).
Not counted: padding of a prefill chunk to its bucket, masked slots of a
decode window, the embedding lookup, norms, rotary and softmax.
"""
from __future__ import annotations


def layer_matmul(conf: dict) -> int:
    """Matrix-product operations of one layer for one token."""
    d, f = conf["hidden_size"], conf["intermediate_size"]
    hq = conf["num_attention_heads"] * conf["head_dim"]
    hkv = conf["num_key_value_heads"] * conf["head_dim"]
    return 2 * (d * hq + 2 * d * hkv + hq * d + 3 * d * f)


def attention(conf: dict, start: int, n: int) -> int:
    """Attention operations of one layer for the tokens at positions
    ``start .. start + n - 1``, each reading its ``position + 1`` keys."""
    keys = n * start + n * (n + 1) // 2
    return 4 * conf["num_attention_heads"] * conf["head_dim"] * keys


def head(conf: dict) -> int:
    return 2 * conf["hidden_size"] * conf["vocab_size"]


def tokens(conf: dict, start: int, n: int) -> int:
    """Operations of ``n`` tokens fed at positions ``start ..``: every
    layer's matrix products and attention (no LM head)."""
    layers = conf["num_hidden_layers"]
    return layers * (n * layer_matmul(conf) + attention(conf, start, n))


def served(conf: dict, prompt: int, first: int, count: int) -> int:
    """Operations behind output tokens ``first .. first + count - 1`` of a
    request with a ``prompt``-token prompt: token 0 needs the whole prompt
    fed, token ``j >= 1`` needs token ``j - 1`` fed at position
    ``prompt + j - 1``; each needs one LM head."""
    if count <= 0:
        return 0
    ops = count * head(conf)
    if first == 0:
        ops += tokens(conf, 0, prompt)
        first, count = 1, count - 1
    return ops + tokens(conf, prompt + first - 1, count)


def decode_attention(conf: dict, prompt: int, first: int, count: int):
    """Operations and bytes of decode attention behind output tokens
    ``first .. first + count - 1`` (token 0 comes from the prompt and has
    none): each decode token fed at position ``p`` reads the ``p + 1``
    cached keys and values of every layer, ``kv_heads x head_dim`` each,
    in the configuration's dtype (2 bytes)."""
    lo = max(first, 1)
    n = first + count - lo
    if n <= 0:
        return 0, 0
    start = prompt + lo - 1
    keys = n * start + n * (n + 1) // 2
    layers = conf["num_hidden_layers"]
    kv = conf["num_key_value_heads"] * conf["head_dim"]
    ops = layers * attention(conf, start, n)
    return ops, layers * keys * kv * 2 * 2
