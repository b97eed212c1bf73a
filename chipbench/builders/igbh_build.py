"""A typed graph at IGBH's shapes, its tables, labels and weights, made
from ``--seed``: the graph in bulk on the host, the tables on the
device.

IGBH (Khatua et al. 2023) has four node types and four relations,
``paper cites paper``, ``paper written_by author``, ``author
affiliated_to institute`` and ``paper topic fos``; GraphLearn-for-
PyTorch's `examples/igbh` trains on every cross-type relation also
reversed and on ``cites`` in both directions with one self-loop per
paper, seven CSRs in all (what `graphlearn_tpu.data.igbh.load_igbh_dir`
builds by default).  This file makes a graph of that shape from the
seed — IGBH itself cannot be fetched here — with the recipe of
`chipbench/build.host_coo` per relation: uniform sources, 30 % of the targets
squared-uniform (hubs: on ``cites`` and, through the transposition, on
the reversed relations), rows sorted and columns ascending within a
row.  Multi-edges and the few random self-loops stay in (the reference
recipe coalesces them; at these sizes they are under a ten-thousandth
of the edges).

Feature tables are MADE in bfloat16 (the configuration's stored
precision), so the program and the plain reference read the same bits.
Weights come in the layout-free form the plain reference reads
(`igbh_reference`): per layer ``{message relation: dict(w=, a_src=,
a_dst=)}``, keyed by the relation as a batch emits it — the sampler
draws ``(s, rel, d)`` outwards from the seeds and hands its edges over
reversed (`message_type`), found node to asking node — and a head
``dict(w=, b=)``.
"""
import sys

import numpy as np

from chipbench import build


def message_type(etype):
  """``(b, rev_rel, a)`` of ``(a, rel, b)``: the reversal rule of
  GraphLearn's typing (``rev_`` added or stripped; a relation within
  one type keeps its name)."""
  a, rel, b = etype
  if a != b:
    rel = rel[4:] if rel.startswith('rev_') else 'rev_' + rel
  return (b, rel, a)


def sizes(cfg):
  return {t: int(n) for t, n in cfg['num_nodes'].items()}


def stored_relations(cfg):
  """The seven relations the sampler draws from, in the order of the
  configuration: each base relation, then its reverse (``cites``, made
  symmetric, is its own)."""
  out = []
  for r in cfg['relations']:
    et = tuple(r['type'])
    out.append(et)
    if et[0] != et[2]:
      out.append(message_type(et))
  return out


def layer_dims(cfg):
  d, h = int(cfg['feature_dim']), int(cfg['hidden'])
  return [d] + [h] * (int(cfg['num_layers']) - 1)


def _host_csr(key, n):
  """``(indptr, indices)`` on the device from a host COO packed as
  ``row << 32 | col``: one in-place sort (rows ascending, columns
  ascending within a row); few and narrow temporaries, because fresh
  host memory is what costs here."""
  import jax.numpy as jnp
  key.sort()
  starts = np.arange(n + 1, dtype=np.uint64) << np.uint64(32)
  indptr = np.searchsorted(key, starts).astype(np.int32)
  low = key.view(np.uint32)[::2] if sys.byteorder == 'little' else (
      key.view(np.uint32)[1::2])
  return jnp.asarray(indptr), jnp.asarray(low.astype(np.int32))


def _packed(rows, cols, out=None):
  out = np.empty(rows.shape[0], np.uint64) if out is None else out
  np.left_shift(rows, 32, out=out, dtype=np.uint64, casting='unsafe')
  np.bitwise_or(out, cols, out=out, casting='unsafe')
  return out


def graphs(cfg, seed):
  """``{stored relation: (indptr, indices)}`` as device arrays.  Drawn
  and sorted on the HOST, in bulk (the seven CSRs hold 51 M entries):
  the chip's compiler takes a minute or more per sort of this size,
  four times over on a cold cache, and a run has six."""
  n = sizes(cfg)
  rng = np.random.default_rng([build.fold_seed(seed), 0])
  out = {}
  for r in cfg['relations']:
    a, _, b = et = tuple(r['type'])
    na, nb = n[a], n[b]
    e = int(round(float(r['avg_degree']) * na))
    rows = rng.integers(0, na, e, dtype=np.uint32)
    u = rng.random(e, np.float32)
    np.multiply(u, u, out=u, where=rng.random(e, np.float32) < 0.3)
    cols = np.minimum((u * np.float32(nb)).astype(np.uint32), nb - 1)
    if a == b:
      # both directions and one self-loop per node
      key = np.empty(2 * e + na, np.uint64)
      _packed(rows, cols, key[:e])
      _packed(cols, rows, key[e:2 * e])
      loop = np.arange(na, dtype=np.uint32)
      _packed(loop, loop, key[2 * e:])
      out[et] = _host_csr(key, na)
    else:
      out[et] = _host_csr(_packed(rows, cols), na)
      out[message_type(et)] = _host_csr(_packed(cols, rows), nb)
  return out


def tables(cfg, seed):
  """``dict(graphs=, feats={type: bf16[n, d]}, labels=int32[n_target])``;
  the largest table first, so that its generator's temporaries find
  the chip empty."""
  import jax
  import jax.numpy as jnp
  n, d = sizes(cfg), int(cfg['feature_dim'])
  key = jax.random.fold_in(jax.random.key(build.fold_seed(seed)), 1)
  dtype = jnp.dtype(cfg['precision']['table'])
  order = sorted(n, key=lambda t: -n[t])
  feats = {}
  for t in order:
    make = jax.jit(lambda k, rows=n[t]: jax.random.uniform(
        k, (rows, d), dtype, -1.0, 1.0))
    feats[t] = make(jax.random.fold_in(key, sorted(n).index(t)))
    feats[t].block_until_ready()
  labels = jax.jit(lambda k: jax.random.randint(
      k, (n[cfg['target']],), 0, int(cfg['classes']), jnp.int32))(
          jax.random.fold_in(key, len(n)))
  return dict(graphs=graphs(cfg, seed),
              feats={t: feats[t] for t in sorted(feats)}, labels=labels)


def weights(cfg, seed):
  """``dict(layers=[{message relation: dict(w=, a_src=, a_dst=)}],
  head=dict(w=, b=))`` as host float32: normal(0, 1/sqrt(fan_in))
  kernels and attention vectors, a normal(0, 0.01) bias."""
  rng = np.random.default_rng(build.fold_seed(seed) + 1)
  normal = lambda shape, scale: (
      rng.standard_normal(shape) * scale).astype(np.float32)
  heads, hidden = int(cfg['heads']), int(cfg['hidden'])
  f = hidden // heads
  rels = sorted(message_type(et) for et in stored_relations(cfg))
  layers = [{rel: dict(w=normal((d, hidden), d ** -0.5),
                       a_src=normal((heads, f), f ** -0.5),
                       a_dst=normal((heads, f), f ** -0.5))
             for rel in rels} for d in layer_dims(cfg)]
  return dict(layers=layers,
              head=dict(w=normal((hidden, int(cfg['classes'])),
                                 hidden ** -0.5),
                        b=normal((int(cfg['classes']),), 0.01)))
