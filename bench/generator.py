"""The one traffic generator: concrete inputs from a mix's parameters
(``bench/traffic/<name>.json``) and the run's seed.

Every seed gets the same sizes and the same arrival rule; the seed
changes only the data (weights, images, snapshots, draws), so runs with
different seeds do the same amount of work.
"""

from __future__ import annotations


def seed_key(seed: int, *path: int):
    """A typed PRNG key from the whole seed (every bit of a seed wider
    than 32 bits counts), folded with ``path``."""
    import jax
    k = jax.random.fold_in(jax.random.key(seed % 2 ** 32), seed // 2 ** 32)
    for p in path:
        k = jax.random.fold_in(k, p)
    return k


#: Streams folded into the seed: one per kind of input.
WEIGHTS, PRODUCER, EPOCHS, IMAGES = 0, 1, 2, 3


def send_time(tf: dict, ready: float) -> float:
    """When a client sends its next request, its previous response having
    been in hand since ``ready``: ``think_s`` later (a closed loop)."""
    return ready + tf["think_s"]


def images(seed: int, tf: dict, shape: tuple[int, ...]):
    """Every client's pool of request images, [clients, images_per_client,
    *shape], standard normal, drawn on the device in one call.  Request
    ``s`` of client ``c`` sends image ``s % images_per_client``."""
    import jax
    n = (tf["clients"], tf["images_per_client"])
    return jax.jit(lambda k: jax.random.normal(k, n + tuple(shape)))(
        seed_key(seed, IMAGES))


def epoch_key(seed: int, epoch: int):
    """The rng of the trainer's ``epoch``-th fused epoch."""
    return seed_key(seed, EPOCHS, epoch)


def producer_key(seed: int, rank: int | None = None):
    """The flat-plate modes of one producer rank (``None``: the single
    producer of a training mix)."""
    return seed_key(seed, PRODUCER) if rank is None \
        else seed_key(seed, PRODUCER, rank)


def weights_key(seed: int):
    return seed_key(seed, WEIGHTS)
