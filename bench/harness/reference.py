"""The plain reference: TN-KDE by direct summation over events.

Independent of the program under test: it takes the network and the events
as the benchmark generated them and evaluates the paper's definition
(arXiv:2501.07106, Defs 3.1-3.4, the network-KDE convention of §3.2):

    F(q, t) = sum_i  K_s(d(q, p_i) / b_s) * K_t(|t - t_i| / b_t)

over lixel centers q (each edge cut into ceil(len / g) segments of length
g, the last one shorter; q at the segment's middle), with triangular
kernels K(x) = max(0, 1 - x). The network distance from q on edge
a = (v_a, v_b) to an event at x_p on edge e = (v_c, v_d) is

    min(d(q, v_c) + x_p, d(q, v_d) + len_e - x_p),
    d(q, v) = min(x_q + D(v_a, v), len_a - x_q + D(v_b, v)),

with D the shortest-path distance between vertices, and |x_q - x_p| when
e = a. No index, no planning, no cache: events are laid out per edge in
blocks of ``BLOCK`` slots, and every block is evaluated against every
lixel on the device, accumulating [L, T] in the stated dtype.

``dtype`` is the arithmetic of the whole evaluation: float32 is the
reference; bfloat16 is the control that the comparison must reject.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp
import scipy.sparse.csgraph as csgraph

from .data import EventSet, Network

BLOCK = 128  # event slots per block (one edge per block)
BLOCKS_PER_STEP = 8
MAX_WINDOWS = 256  # windows per device pass


def lixels(net: Network, g: float):
    """(edge id, center position) of every lixel, edge-major, ascending."""
    counts = np.ceil(net.length / g).astype(np.int64)
    edge = np.repeat(np.arange(net.n_edges), counts)
    first = np.repeat(np.cumsum(counts) - counts, counts)
    start = (np.arange(int(counts.sum())) - first) * g
    end = np.minimum(start + g, net.length[edge])
    return edge, (start + end) / 2.0


def vertex_distances(net: Network, radius: float) -> np.ndarray:
    """D(u, v) for all vertex pairs, +inf beyond ``radius`` (float64 [V, V])."""
    rows = np.concatenate([net.src, net.dst])
    cols = np.concatenate([net.dst, net.src])
    w = np.concatenate([net.length, net.length])
    # a parallel edge is only as good as its shortest copy
    order = np.lexsort((w, cols, rows))
    r, c, d = rows[order], cols[order], w[order]
    keep = np.ones(len(r), bool)
    keep[1:] = (r[1:] != r[:-1]) | (c[1:] != c[:-1])
    adj = sp.csr_matrix((d[keep], (r[keep], c[keep])),
                        shape=(net.n_vertices, net.n_vertices))
    return csgraph.dijkstra(adj, directed=False, limit=radius)


def _size_class(n: int, quantum: int) -> int:
    """Round n up to a power of two of ``quantum``s, and above 1024 quanta
    to a multiple of 128 quanta: few shapes, so few compiles."""
    q = -(-max(int(n), 1) // quantum)
    q = 1 << (q - 1).bit_length() if q <= 1024 else -(-q // 128) * 128
    return q * quantum


def _split_time(t: np.ndarray, t_ref: float):
    """Seconds after ``t_ref`` as (multiple of 1024, remainder): the
    difference of two such pairs is exact in float32 up to the remainder's
    rounding, where one float32 of 7.8e6 s would round to half a second."""
    rel = np.asarray(t, np.float64) - t_ref
    hi = np.round(rel / 1024.0) * 1024.0
    return hi, rel - hi


class Reference:
    def __init__(self, net: Network, *, g: float, b_s: float, b_t: float,
                 dtype: str = "float32"):
        import jax.numpy as jnp

        self.net, self.b_s, self.b_t = net, float(b_s), float(b_t)
        self.dtype = jnp.dtype(dtype)
        self.lix_edge, self.lix_x = lixels(net, g)
        D = vertex_distances(net, self.b_s)
        a, b = net.src[self.lix_edge], net.dst[self.lix_edge]
        x = self.lix_x[:, None]
        # d(q, v) for every lixel and vertex, [V, L] so a vertex's row is
        # contiguous for the per-block lookups
        dq = np.minimum(x + D[a], (net.length[self.lix_edge][:, None] - x) + D[b])
        self._dqT = jnp.asarray(dq.T, self.dtype)
        self._lix_edge = jnp.asarray(self.lix_edge, jnp.int32)
        self._lix_x = jnp.asarray(self.lix_x, self.dtype)
        self._fn = None

    @property
    def n_lixels(self) -> int:
        return int(self.lix_edge.shape[0])

    def _blocks(self, ev: EventSet, t_ref: float):
        """Events per edge, padded to whole blocks of BLOCK slots."""
        order = np.argsort(ev.edge, kind="stable")
        edge, pos, time = ev.edge[order], ev.pos[order], ev.time[order]
        counts = np.bincount(edge, minlength=self.net.n_edges)
        nblk = -(-counts // BLOCK)
        blk_edge = np.repeat(np.arange(self.net.n_edges), nblk)
        nb = int(nblk.sum())
        nb_pad = _size_class(nb, BLOCKS_PER_STEP)
        first_blk = np.cumsum(nblk) - nblk
        first_ev = np.cumsum(counts) - counts
        rank = np.arange(ev.n) - first_ev[edge]
        slot = (first_blk[edge] + rank // BLOCK) * BLOCK + rank % BLOCK
        shape = (nb_pad * BLOCK,)
        P = np.zeros(shape)
        valid = np.zeros(shape, bool)
        hi, lo = _split_time(time, t_ref)
        H = np.zeros(shape)
        Lo = np.zeros(shape)
        P[slot], valid[slot], H[slot], Lo[slot] = pos, True, hi, lo
        be = np.zeros(nb_pad, np.int64)
        be[:nb] = blk_edge
        bvalid = np.zeros(nb_pad, bool)
        bvalid[:nb] = True
        shp = (nb_pad // BLOCKS_PER_STEP, BLOCKS_PER_STEP)
        return dict(
            edge=be.reshape(shp), live=bvalid.reshape(shp),
            vc=self.net.src[be].reshape(shp), vd=self.net.dst[be].reshape(shp),
            len=self.net.length[be].reshape(shp),
            pos=P.reshape(shp + (BLOCK,)), valid=valid.reshape(shp + (BLOCK,)),
            t_hi=H.reshape(shp + (BLOCK,)), t_lo=Lo.reshape(shp + (BLOCK,)),
        )

    def _compile(self):
        import jax
        import jax.numpy as jnp

        dt = self.dtype
        b_s, b_t = self.b_s, self.b_t
        hp = jax.lax.Precision.HIGHEST

        def step(acc, blk, dqT, lix_edge, lix_x, w_hi, w_lo):
            dc = dqT[blk["vc"]]  # [B, L]
            dd = dqT[blk["vd"]]
            pos = blk["pos"].astype(dt)[:, None, :]  # [B, 1, C]
            ln = blk["len"].astype(dt)[:, None, None]
            d = jnp.minimum(dc[:, :, None] + pos, dd[:, :, None] + (ln - pos))
            same = (lix_edge[None, :] == blk["edge"][:, None])[:, :, None]
            d = jnp.where(same, jnp.abs(lix_x[None, :, None] - pos), d)
            ks = jnp.maximum(jnp.asarray(1, dt) - d / jnp.asarray(b_s, dt), 0)
            ks = jnp.where((blk["valid"] & blk["live"][:, None])[:, None, :],
                           ks, jnp.asarray(0, dt))
            dtm = ((blk["t_hi"].astype(dt)[:, :, None] - w_hi[None, None, :])
                   + (blk["t_lo"].astype(dt)[:, :, None] - w_lo[None, None, :]))
            kt = jnp.maximum(jnp.asarray(1, dt) - jnp.abs(dtm) / jnp.asarray(b_t, dt), 0)
            part = jnp.einsum("blc,bct->lt", ks, kt, precision=hp,
                              preferred_element_type=dt)
            return acc + part, None

        def run(blocks, dqT, lix_edge, lix_x, w_hi, w_lo):
            acc = jnp.zeros((lix_x.shape[0], w_hi.shape[0]), dt)
            acc, _ = jax.lax.scan(
                lambda a, b: step(a, b, dqT, lix_edge, lix_x, w_hi, w_lo),
                acc, blocks)
            return acc.T

        self._fn = jax.jit(run)

    def heat(self, ev: EventSet, ts) -> np.ndarray:
        """F [len(ts), L] for the event set ``ev`` (float64 on the host)."""
        import jax.numpy as jnp

        ts = np.asarray(ts, np.float64)
        out = np.zeros((len(ts), self.n_lixels))
        if ev.n == 0 or len(ts) == 0:
            return out
        if self._fn is None:
            self._compile()
        t_ref = float(np.min(ev.time))
        blocks = {k: jnp.asarray(v) for k, v in self._blocks(ev, t_ref).items()}
        for lo in range(0, len(ts), MAX_WINDOWS):
            chunk = ts[lo:lo + MAX_WINDOWS]
            pad = np.full(_size_class(len(chunk), 1), chunk[0])
            pad[:len(chunk)] = chunk
            w_hi, w_lo = _split_time(pad, t_ref)
            F = self._fn(blocks, self._dqT, self._lix_edge, self._lix_x,
                         jnp.asarray(w_hi, self.dtype), jnp.asarray(w_lo, self.dtype))
            out[lo:lo + len(chunk)] = np.asarray(F, np.float64)[:len(chunk)]
        return out
