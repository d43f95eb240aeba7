"""Decoder-only causal language model (GPT-2 style), built from the
same fluid layer surface as the other model families.

The reference era predates GPT as a shipped model, but its framework
contract — program + layers + executor — is exactly what a causal LM
needs; this family exists to exercise the long-context machinery
(causal Pallas flash attention, ring/sequence parallelism) as a model
users expect to find.  Blocks are pre-LN (x + attn(ln(x)),
x + mlp(ln(x))); attention is `bert.multi_head_attention(causal=True)`
so the seq >= flash_min_len dispatch, kernels, and masks are shared
with the encoder stack.
"""

import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers

from . import bert as _bert


class GptConfig(object):
    def __init__(self, vocab_size=50257, hidden=768, layers=12,
                 heads=12, intermediate=None, max_pos=1024,
                 dropout=0.1, attn_dropout=None, use_flash=True,
                 moe_experts=0, moe_hidden=None, moe_aux_weight=0.01,
                 moe_capacity_factor=2.0, moe_top_k=1,
                 use_context_parallel=False):
        self.vocab_size = vocab_size
        self.hidden = hidden
        self.layers = layers
        self.heads = heads
        self.intermediate = intermediate or 4 * hidden
        self.max_pos = max_pos
        self.dropout = dropout
        self.attn_dropout = dropout if attn_dropout is None else \
            attn_dropout
        self.use_flash = use_flash
        self.flash_min_len = 512
        # MoE FFN blocks (layers.moe's capacity-based path: Switch
        # top-1 by default, GShard top-2 with moe_top_k=2): moe_experts > 0
        # swaps the dense MLP for an expert-parallel MoE that shards
        # over an 'ep' mesh axis under CompiledProgram.with_mesh
        self.moe_experts = moe_experts
        self.moe_hidden = moe_hidden or self.intermediate
        self.moe_aux_weight = moe_aux_weight
        self.moe_capacity_factor = moe_capacity_factor
        self.moe_top_k = moe_top_k
        # route attention through layers.context_parallel_attention
        # (ring attention over the 'sp' axis on a mesh; dense fallback
        # on one device)
        self.use_context_parallel = use_context_parallel


BASE = GptConfig()
TINY = GptConfig(vocab_size=97, hidden=64, layers=2, heads=4,
                 max_pos=128, dropout=0.0)


def decoder_block(x, cfg, is_test, aux_losses=None):
    """Pre-LN GPT-2 block; with cfg.moe_experts the MLP is a
    capacity-based MoE FFN (Switch top-1 / GShard top-2) and its
    load-balance loss is appended to aux_losses."""
    a = layers.layer_norm(x, begin_norm_axis=2)
    a = _bert.multi_head_attention(a, None, cfg, is_test, causal=True)
    if not is_test and cfg.dropout:
        a = layers.dropout(a, cfg.dropout, is_test=is_test,
                           dropout_implementation='upscale_in_train')
    x = layers.elementwise_add(x, a)
    m = layers.layer_norm(x, begin_norm_axis=2)
    if cfg.moe_experts:
        m, aux = layers.moe(m, num_experts=cfg.moe_experts,
                            hidden_size=cfg.moe_hidden,
                            capacity_factor=cfg.moe_capacity_factor,
                            aux_weight=cfg.moe_aux_weight,
                            top_k=cfg.moe_top_k)
        if aux_losses is not None:
            aux_losses.append(aux)
    else:
        m = layers.fc(m, size=cfg.intermediate, num_flatten_dims=2,
                      act='gelu')
        m = layers.fc(m, size=cfg.hidden, num_flatten_dims=2)
    if not is_test and cfg.dropout:
        m = layers.dropout(m, cfg.dropout, is_test=is_test,
                           dropout_implementation='upscale_in_train')
    return layers.elementwise_add(x, m)


def gpt_decoder(ids, pos_ids, cfg, is_test=False, aux_losses=None):
    tok = layers.embedding(ids, size=[cfg.vocab_size, cfg.hidden],
                           param_attr=fluid.ParamAttr(name='gpt_wte'))
    pos = layers.embedding(pos_ids, size=[cfg.max_pos, cfg.hidden])
    x = layers.elementwise_add(tok, pos)
    if not is_test and cfg.dropout:
        x = layers.dropout(x, cfg.dropout, is_test=is_test,
                           dropout_implementation='upscale_in_train')
    for _ in range(cfg.layers):
        x = decoder_block(x, cfg, is_test, aux_losses=aux_losses)
    return layers.layer_norm(x, begin_norm_axis=2)


def build_lm(cfg=None, seq_len=128, is_test=False):
    """Next-token LM: feeds ids/pos/labels, returns (feeds, logits,
    loss).  labels are the inputs shifted left by the caller;
    ignore_index=-1 masks padding and the final position."""
    cfg = cfg or BASE
    ids = fluid.layers.data('ids', shape=[seq_len], dtype='int64')
    pos = fluid.layers.data('pos_ids', shape=[seq_len], dtype='int64')
    labels = fluid.layers.data('labels', shape=[seq_len], dtype='int64')
    aux_losses = []
    h = gpt_decoder(ids, pos, cfg, is_test, aux_losses=aux_losses)
    logits = layers.fc(h, size=cfg.vocab_size, num_flatten_dims=2)
    loss = layers.softmax_with_cross_entropy(
        logits, layers.unsqueeze(labels, [2]), ignore_index=-1)
    loss = layers.mean(loss)
    if not is_test:
        # the load-balance term belongs in the TRAINING objective
        # only; eval loss stays the bare LM cross-entropy so
        # perplexities compare across dense/MoE models
        for aux in aux_losses:
            loss = layers.elementwise_add(loss, aux)
    feeds = {'ids': ids, 'pos_ids': pos, 'labels': labels}
    return feeds, logits, loss


def lm_batch(ids_2d):
    """[B, T] token batch -> feed dict with positions and shifted
    labels (last position ignored)."""
    ids_2d = np.asarray(ids_2d, 'int64')
    b, t = ids_2d.shape
    pos = np.tile(np.arange(t, dtype='int64'), (b, 1))
    labels = np.full((b, t), -1, 'int64')
    labels[:, :-1] = ids_2d[:, 1:]
    return {'ids': ids_2d, 'pos_ids': pos, 'labels': labels}


def greedy_generate(exe, infer_prog, logits_var, prompt, steps, cfg,
                    scope=None):
    """Host-driven greedy decoding: re-scores the growing prefix padded
    to max_pos each step (one executable total; the executor re-traces
    only if the padded length changes).  prompt: [T0] ints with
    T0 < cfg.max_pos.  Returns the full generated id list — possibly
    fewer than `steps` new tokens if the context fills max_pos first."""
    toks = list(int(t) for t in np.asarray(prompt).ravel())
    t_max = cfg.max_pos
    if len(toks) >= t_max:
        raise ValueError(
            'prompt length %d must be < cfg.max_pos (%d)'
            % (len(toks), t_max))
    for _ in range(steps):
        cur = len(toks)
        ids = np.zeros((1, t_max), 'int64')
        ids[0, :cur] = toks
        feed = {'ids': ids,
                'pos_ids': np.arange(t_max, dtype='int64')[None, :],
                'labels': np.full((1, t_max), -1, 'int64')}
        out, = exe.run(infer_prog, feed=feed,
                       fetch_list=[logits_var], scope=scope)
        nxt = int(np.asarray(out)[0, cur - 1].argmax())
        toks.append(nxt)
        if len(toks) >= t_max:
            break
    return toks


def synthetic_batch(cfg, batch, seq_len, rng):
    ids = rng.randint(0, cfg.vocab_size, (batch, seq_len))
    return lm_batch(ids)
