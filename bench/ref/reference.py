"""Plain references for the benchmark's output checks.

Nothing here imports the program or takes anything it made.  The pieces:

* ``ref_pairwise``: float64 distance matrix of two small point sets
  (numpy), copied from ``chip_smoke.ref_pairwise``;
* ``_near``: distance from each row to its nearest centre, in f32 on the
  device (after ``chip_smoke.ref_radius``): direct coordinate differences
  for euclidean, the normalized dot at ``HIGHEST`` for cosine;
* ``greedy`` and ``remote_edge``: the GMM prefix solver and the
  remote-edge value, in float64;
* ``RefStream``: the plain streaming core-set (SMM, arXiv 1605.05590 §4),
  one point at a time, in float64 or in f32 at a stated matmul precision.

``precision="default"`` is the lower-precision control: every distance is
a matmul as ``Precision.DEFAULT`` computes it on a TPU (one bf16 pass).
``"high"`` (three bf16 passes) is the step just below the ``HIGHEST`` that
the program states; at this cell's distances it rounds no worse than f32
does (PERF.md), so it stays here as a reading and not as the control.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np



def _split(x):
    hi = x.astype(jnp.bfloat16)
    return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)


def _matmul(a, b, precision):
    """``a @ b`` in f32 at ``HIGHEST``, or as ``HIGH`` (three bf16 passes)
    or ``DEFAULT`` (one) computes it on a TPU with f32 accumulation, spelt
    out so that every backend rounds alike."""
    if precision == "highest":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)

    def mm(x, y):
        return jnp.matmul(x, y, preferred_element_type=jnp.float32)

    if precision == "default":
        return mm(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16))
    if precision != "high":
        raise ValueError(precision)
    (ah, al), (bh, bl) = _split(a), _split(b)
    return mm(ah, bh) + (mm(ah, bl) + mm(al, bh))


# --------------------------------------------------------------------------
# float64 on the host
# --------------------------------------------------------------------------

MAX_DIST = {"cosine": np.pi, "euclidean": np.inf}


def ref_pairwise(a, b, metric):
    """float64 distance matrix of two small point sets (numpy)."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    if metric == "euclidean":
        return np.sqrt(((a[:, None, :] - b[None, :, :]) ** 2).sum(-1))
    if metric == "cosine":
        a = a / np.linalg.norm(a, axis=1, keepdims=True)
        b = b / np.linalg.norm(b, axis=1, keepdims=True)
        return np.arccos(np.clip(a @ b.T, -1.0, 1.0))
    raise ValueError(metric)


def remote_edge(dm) -> float:
    """Smallest off-diagonal entry of a square distance matrix."""
    dm = np.array(dm, np.float64)
    np.fill_diagonal(dm, np.inf)
    return float(dm.min())


def greedy(dm, k: int) -> np.ndarray:
    """GMM prefix (Tamir's 2-approximation for remote-edge): start at row 0,
    then take the row farthest from the rows taken, first index on ties."""
    dm = np.asarray(dm)
    sel = [0]
    near = dm[0].copy()
    for _ in range(k - 1):
        j = int(np.argmax(near))
        sel.append(j)
        near = np.minimum(near, dm[j])
    return np.asarray(sel, np.int64)


# --------------------------------------------------------------------------
# f32 on the device
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("metric",))
def _prep_device(x, metric):
    """Rows as the distances take them: unit rows under cosine."""
    x = x.astype(jnp.float32)
    if metric == "cosine":
        return x / jnp.linalg.norm(x, axis=1, keepdims=True)
    return x


@functools.partial(jax.jit, static_argnames=("metric", "precision"))
def _dist_device(a, b, metric, precision):
    """(n, m) distances of prepared rows, every cross term a matmul at
    ``precision``."""
    if metric == "euclidean":
        d2 = (jnp.sum(a * a, 1)[:, None] + jnp.sum(b * b, 1)[None, :]
              - 2.0 * _matmul(a, b.T, precision))
        return jnp.sqrt(jnp.maximum(d2, 0.0))
    if metric == "cosine":
        return jnp.arccos(jnp.clip(_matmul(a, b.T, precision), -1.0, 1.0))
    raise ValueError(metric)


def device_dist(x, c, metric, precision):
    """(n, m) distances, every cross term a matmul at ``precision``."""
    return _dist_device(_prep_device(x, metric), _prep_device(c, metric),
                        metric, precision)


def _near(x, cen, metric):
    """Distance from each row of ``x`` to its nearest centre: coordinate
    differences for euclidean, the normalized dot at HIGHEST for cosine."""
    if metric == "euclidean":
        return jnp.min(jnp.sqrt(jnp.sum(
            (x[:, None, :] - cen[None, :, :]) ** 2, axis=-1)), axis=1)
    return jnp.min(device_dist(x, cen, metric, "highest"), axis=1)


# --------------------------------------------------------------------------
# the streaming core-set, one point at a time
# --------------------------------------------------------------------------

class RefStream:
    """Plain SMM over a stream (remote-edge: centres only).

    State: ``cap = kprime + 1`` centre slots and a threshold ``d``.  The
    first ``cap`` points fill the slots and ``d`` starts at their smallest
    positive pairwise distance.  A merge keeps, in slot order, a maximal
    set of centres more than ``2d`` apart and remembers the others in
    ``M``; while a merge removes nothing, ``d`` doubles and it merges again.
    A later point farther than ``4d`` from every centre takes the first
    free slot; when the slots are full, ``d`` doubles and a merge follows.
    At the end, centres from ``M`` fill up to ``k``.

    ``precision="f64"`` computes every distance in float64 on the host;
    ``"highest"``, ``"high"`` or ``"default"`` on the device in f32 at that
    precision.  Within a chunk each row's distance to the nearest centre
    is kept up to date as centres arrive, which is the point-by-point rule
    with one distance matrix a chunk.  Once ``4d`` exceeds every distance
    the metric can give (pi under cosine), no point is far again and a
    chunk is only counted."""

    def __init__(self, k: int, kprime: int, metric: str,
                 precision: str = "f64"):
        self.k, self.cap, self.metric = k, kprime + 1, metric
        self.precision = precision
        self.T = None
        self.valid = np.zeros(self.cap, bool)
        self.M = None
        self.m_valid = np.zeros(self.cap, bool)
        self.d = None
        self.prefix = []
        self.n_seen = 0

    def prep(self, x):
        """Rows as ``pdist`` takes them: float64 on the host, or f32 on the
        device; unit rows under cosine."""
        if self.precision != "f64":
            return _prep_device(jnp.asarray(x, jnp.float32), self.metric)
        x = np.asarray(x, np.float64)
        if self.metric == "cosine":
            return x / np.linalg.norm(x, axis=1, keepdims=True)
        return x

    def pdist(self, ap, bp):
        """Distances of prepared rows, as a float64 array on the host."""
        if self.precision != "f64":
            return np.asarray(_dist_device(ap, bp, self.metric,
                                           self.precision), np.float64)
        if self.metric == "cosine":
            return np.arccos(np.clip(ap @ bp.T, -1.0, 1.0))
        return np.sqrt(((ap[:, None, :] - bp[None, :, :]) ** 2).sum(-1))

    def dist(self, a, b):
        return self.pdist(self.prep(a), self.prep(b))

    def _merge(self):
        dm = self.pdist(self.Tp, self.Tp)
        keep = np.zeros(self.cap, bool)
        covered = np.zeros(self.cap, bool)
        for j in range(self.cap):
            if self.valid[j] and not covered[j]:
                keep[j] = True
                covered |= dm[j] <= 2.0 * self.d
        self.m_valid = self.valid & ~keep
        self.M = self.T.copy()
        self.valid = keep

    def _merge_until_room(self):
        self._merge()
        while self.valid.sum() >= self.cap:
            self.d *= 2.0
            self._merge()

    def _boot(self, pts):
        self.T = np.array(pts, np.float64)
        self.Tp = self.prep(self.T)
        self.valid[:] = True
        dm = self.pdist(self.Tp, self.Tp)
        np.fill_diagonal(dm, np.inf)
        pos = dm[dm > 0]
        self.d = float(pos.min()) if pos.size else 1e-30
        self._merge_until_room()

    def centres(self):
        return self.T[self.valid]

    def _near(self, xp):
        """Each prepared row's distance to its nearest centre (every slot
        is computed, so that the shapes stay fixed)."""
        dm = self.pdist(xp, self.Tp)
        dm[:, ~self.valid] = np.inf
        return dm.min(axis=1)

    def update(self, chunk) -> None:
        chunk = np.asarray(chunk, np.float64)
        self.n_seen += chunk.shape[0]
        if self.T is None:
            need = self.cap - sum(len(p) for p in self.prefix)
            self.prefix.append(chunk[:need])
            chunk = chunk[need:]
            if sum(len(p) for p in self.prefix) < self.cap:
                return
            self._boot(np.concatenate(self.prefix))
            self.prefix = []
        if chunk.shape[0] == 0 or 4.0 * self.d > MAX_DIST[self.metric]:
            return
        xp = self.prep(chunk)
        near = self._near(xp)
        pos = 0
        while True:
            far = np.nonzero(near[pos:] > 4.0 * self.d)[0]
            if far.size == 0:
                return
            pos += int(far[0])
            free = int(np.argmin(self.valid))
            new = chunk[pos:pos + 1]
            self.T[free] = new[0]
            self.valid[free] = True
            if self.precision == "f64":
                self.Tp[free] = self.prep(new)[0]
            else:
                self.Tp = self.prep(self.T)
            pos += 1
            full = self.valid.all()
            if full:
                self.d *= 2.0
                self._merge_until_room()
            if pos == chunk.shape[0] or 4.0 * self.d > MAX_DIST[self.metric]:
                return
            if full:
                near = self._near(xp)
            else:                         # one centre more
                near = np.minimum(near, self.pdist(xp, self.prep(new))[:, 0])

    def finalize(self):
        """(core-set rows in slot order, certified radius 4d)."""
        for j in range(self.cap):
            if self.valid.sum() >= self.k:
                break
            if self.m_valid[j]:
                free = int(np.argmin(self.valid))
                self.T[free] = self.M[j]
                self.valid[free] = True
        return self.T[self.valid].copy(), 4.0 * self.d
