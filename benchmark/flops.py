"""Operations counted from the graph, not quoted from a paper.

``graph_macs(symbol, **input_shapes)`` walks the symbol's own nodes with the
shapes the symbol infers and counts the multiply-accumulates of every
Convolution, FullyConnected, dot/batch_dot and MultiHeadAttention node. A
FLOP count is 2 x MACs (the chip's peak counts multiply and add apart); a
training step is 3 x the forward (backward is two matrix products per forward
one); recomputation is not credited. Elementwise work, normalisation and
pooling are not counted: they are bytes, not matrix operations.

bench.py's ``_TRAIN_FLOPS_PER_IMG = 3 * 4.09e9`` called 4.09e9 "2*MACs"; the
resnet-50 symbol has 4,089,184,256 MACs per image, i.e. 8.18 GFLOP forward,
so every MFU worked out from that constant is half of this count's.
"""
import json
import math


def _prod(xs):
    return math.prod(int(x) for x in xs)


def _shape_attr(value):
    return tuple(int(x) for x in str(value).strip("()[] ").split(",") if
                 x.strip())


def _node_macs(op, attrs, ins, outs):
    """MACs of one node from its input and output shapes, or None when the
    node is not a matrix operation."""
    if op == "Convolution":
        kernel = _shape_attr(attrs["kernel"])
        groups = int(attrs.get("num_group", 1))
        return _prod(outs[0]) * (ins[0][1] // groups) * _prod(kernel)
    if op == "FullyConnected":
        return _prod(outs[0]) * ins[1][-1]          # weight is (out, in)
    if op in ("dot", "batch_dot"):
        ta = str(attrs.get("transpose_a", "False")) == "True"
        contracted = ins[0][-2] if ta else ins[0][-1]
        return _prod(outs[0]) * contracted
    if op in ("MultiHeadAttention", "_contrib_MultiHeadAttention"):
        # q (B,H,T,D), k/v (B,H,S,D): scores T x S x D and apply T x S x D,
        # counted dense (a causal lowering computes about half; not credited)
        b, h, t, d = ins[0]
        return 2 * b * h * t * ins[1][2] * d
    return None


def graph_macs(symbol, **input_shapes):
    """(total MACs, {node name: MACs}) of ``symbol`` at ``input_shapes``."""
    graph = json.loads(symbol.tojson())
    nodes, row = graph["nodes"], graph["node_row_ptr"]
    internals = symbol.get_internals()
    _, out_shapes, _ = internals.infer_shape(**input_shapes)
    if out_shapes is None or len(out_shapes) != row[-1]:
        raise ValueError("shape inference did not cover the graph")
    shape_of = lambda nid, idx: tuple(out_shapes[row[nid] + idx])
    per_node = {}
    for nid, node in enumerate(nodes):
        if node["op"] == "null":
            continue
        ins = [shape_of(i, o) for i, o, _ in node["inputs"]]
        outs = [shape_of(nid, k) for k in range(row[nid + 1] - row[nid])]
        macs = _node_macs(node["op"], node.get("attr", {}), ins, outs)
        if macs is not None:
            per_node[node["name"]] = macs
    return sum(per_node.values()), per_node


def train_flops(symbol, **input_shapes):
    """FLOP of one training step at these shapes: 2 x MACs x 3."""
    return 6 * graph_macs(symbol, **input_shapes)[0]


# ---- closed forms, for the tests and for decode (whose graph spells attention
#      out of broadcast ops that carry no MACs the walk above would find)
def transformer_forward_macs(batch, seq, d, layers, ffn, vocab):
    """Decoder-only transformer forward over (batch, seq): per token and
    layer qkv (3d^2), output projection (d^2), FFN (2 d ffn) and dense
    attention (2 seq d), plus the vocabulary head (d vocab)."""
    per_token = layers * (3 * d * d + d * d + 2 * d * ffn + 2 * seq * d) \
        + d * vocab
    return batch * seq * per_token


def decode_step_flops(contexts, d, layers, ffn, vocab):
    """FLOP one decode step needs for lanes at these context lengths:
    2 x the parameters each token touches, plus attention over each lane's
    OWN context (scores and apply, 2 x 2 x context x d per layer)."""
    weights = layers * (4 * d * d + 2 * d * ffn) + d * vocab
    return sum(2 * weights + 4 * layers * int(c) * d for c in contexts)
