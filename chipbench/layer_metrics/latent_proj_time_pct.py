"""What a latent mixer has that plain attention has not, forward, backward
and update: the two latent products (hidden to latent + shared rotated key,
latent to every head's keys and values), the latent's norm, the splits, the
shared key spread over the heads and joined to each head's own part, the
key's head norm and both partial rotaries: share of the device's busy time
under ``layer<i>.mixer.latent`` and ``mtp.mixer.latent``
(``chipbench/scope_time.py``).  None where nothing carries such a path: a
model without latent mixers, or the parent of the PR that added them."""

from chipbench import scope_time


def value(run):
    return scope_time.pct(scope_time.share(
        run, ("layer*.mixer.latent", "mtp.mixer.latent")))
