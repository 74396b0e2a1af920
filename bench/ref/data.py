"""Seeded data generators, run on the device: ``feed_chunk`` makes one chunk
of a feed of embeddings in which new topics keep arriving.

A feed has ``1 + new_per_chunk * insert_chunks`` topics.  Topics come in
domains of ``per_domain`` siblings: a domain centre is a random unit
vector, a topic centre lies ``sibling_spread`` (a noise norm) off it, and a
row lies ``topic_spread`` off its topic centre.  Chunk ``j`` draws its rows
uniformly from the topics that arrived in earlier chunks; while
``j < insert_chunks``, ``new_per_chunk`` new topics arrive in it, each
with one row: at ``new_per_chunk - 1`` random rows past the first
``skip``, and at the chunk's last row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def key_of(seed: int, *path: int):
    """PRNG key of a seed of up to 64 bits (``jax.random.key`` keeps only
    the low 32), folded with ``path``.  The bits come from XLA's
    RngBitGenerator ("rbg"), which a TPU draws in hardware."""
    key = jax.random.fold_in(jax.random.key(seed >> 32, impl="rbg"),
                             seed & 0xFFFFFFFF)
    for p in path:
        key = jax.random.fold_in(key, p)
    return key


def _unit(x):
    return x / jnp.linalg.norm(x, axis=-1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("topics", "dim", "per_domain",
                                             "sibling_spread"))
def topic_centres(key, topics: int, dim: int, per_domain: int,
                  sibling_spread: float):
    """``topics`` unit vectors, ``per_domain`` consecutive ones around each
    of ``ceil(topics / per_domain)`` random domain centres."""
    domains = -(-topics // per_domain)
    dc = _unit(jax.random.normal(jax.random.fold_in(key, 0), (domains, dim),
                                 jnp.float32))
    off = jax.random.normal(jax.random.fold_in(key, 1), (topics, dim),
                            jnp.float32)
    return _unit(dc[jnp.arange(topics) // per_domain]
                 + sibling_spread / dim ** 0.5 * off)


@functools.partial(jax.jit, static_argnames=("rows", "new_per_chunk",
                                             "insert_chunks", "skip",
                                             "topic_spread"))
def feed_chunk(key, centres, j, rows: int, new_per_chunk: int,
               insert_chunks: int, skip: int, topic_spread: float):
    """Chunk ``j`` of the feed whose topic centres are ``centres``."""
    dim = centres.shape[1]
    kt, kp, kn = jax.random.split(jax.random.fold_in(jax.random.fold_in(
        key, 2), j), 3)
    m = new_per_chunk
    known = 1 + m * jnp.minimum(j, insert_chunks)
    topic = jnp.minimum(jnp.floor(jax.random.uniform(kt, (rows,)) * known)
                        .astype(jnp.int32), known - 1)
    at = jnp.sort(jnp.concatenate([
        skip + jax.random.choice(kp, rows - 1 - skip, (m - 1,),
                                 replace=False),
        jnp.asarray([rows - 1])]))
    new = jnp.where(j < insert_chunks, known + jnp.arange(m), topic[at])
    topic = topic.at[at].set(new)
    noise = jax.random.normal(kn, (rows, dim), jnp.float32)
    return centres[topic] + topic_spread / dim ** 0.5 * noise
