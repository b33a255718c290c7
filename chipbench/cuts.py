"""A configuration that is one chip's share of a stated deployment.

Most public models need more than one chip's memory, so a configuration may
be CUT: fewer layers than published (the rest lie on further pipeline
stages), and of each layer the experts, heads or vocabulary rows that one of
the chips sharing it would hold.  No width is ever cut.  The rules, checked
before any run and by ``tests/chipbench/test_harness.py::test_configs``:

- ``reduced`` in the configuration's file equals the entry's ``reduced`` in
  ``BENCHMARK.json``, letter for letter: the keys of the file whose value as
  run differs from the source's, each a name (no space, at most 64
  characters), at most 16 of them;
- when it is not empty the file also holds ``published`` (for every cut key
  the source's own value, a whole number larger than the value as run) and
  ``deployment``: ``chips_sharing_a_layer`` (a whole number, 1 or more),
  ``how`` (one sentence on how a layer is divided) and ``cuts``, which gives
  every cut key its ``kind`` and one sentence ``why``;
- the kinds are the cuts the ``model-configs`` guide allows, each with the
  guide's floor on the value as run: ``depth`` (a key that counts layers; at
  least 4), ``experts_held`` (a key that counts experts; at least 8),
  ``vocabulary`` (a key with ``vocab`` in it; at least an eighth of the
  published one, rounded down) and ``heads_held`` (a key that counts heads;
  no floor).  A key that names a width (``*_dim``, ``*_rank``, a hidden,
  intermediate, latent, state, projection, head or window size, an expansion
  factor, the experts per token) is refused whatever kind it claims.
"""

from __future__ import annotations

import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
WIDTH = re.compile(
    r"(_dim|_rank|_width|_factor)$|hidden_size|intermediate|latent|state|"
    r"proj|head_size|window|expansion|per_tok|top_?k|d_model|d_ff")
# kind -> (what the key's name has to hold, floor on the value as run)
KINDS = {
    "depth": ("layers", lambda published: 4),
    "experts_held": ("experts", lambda published: 8),
    "vocabulary": ("vocab", lambda published: published // 8),
    "heads_held": ("heads", lambda published: 1),
}


def _whole(v):
    return isinstance(v, int) and not isinstance(v, bool)


def problems(sizes: dict, entry: dict) -> list:
    """Every rule above that ``sizes`` (a configuration's file as written)
    breaks against its entry in ``BENCHMARK.json``; empty when it is sound."""
    out = []
    reduced = sizes.get("reduced")
    if reduced != entry.get("reduced"):
        out.append(f"reduced differs: the file has {reduced!r}, "
                   f"BENCHMARK.json {entry.get('reduced')!r}")
    if not isinstance(reduced, list) or len(reduced) > 16 or not all(
            isinstance(k, str) and NAME.match(k) for k in reduced):
        return out + ["reduced is a list of at most 16 keys, each a name"]
    if not reduced:
        return out
    published = sizes.get("published")
    deployment = sizes.get("deployment")
    if not isinstance(published, dict):
        return out + ["a cut configuration states `published`"]
    if not isinstance(deployment, dict):
        return out + ["a cut configuration states `deployment`"]
    chips = deployment.get("chips_sharing_a_layer")
    if not _whole(chips) or chips < 1:
        out.append("deployment.chips_sharing_a_layer is a whole number, "
                   "1 or more")
    how = deployment.get("how")
    if not isinstance(how, str) or not 1 <= len(how) <= 200:
        out.append("deployment.how is one sentence of at most 200 "
                   "characters")
    cuts = deployment.get("cuts")
    if not isinstance(cuts, dict):
        return out + ["deployment.cuts gives every cut key its kind and why"]
    for key in reduced:
        run = sizes.get(key)
        if not _whole(run) or run < 1:
            out.append(f"{key}: the value as run is a whole number at the "
                       f"top level of the file, not {run!r}")
            continue
        if key not in published:
            out.append(f"{key}: missing from `published`")
            continue
        pub = published[key]
        if not _whole(pub) or pub <= run:
            out.append(f"{key}: published {pub!r} is not larger than the "
                       f"{run} as run")
            continue
        cut = cuts.get(key)
        kind = cut.get("kind") if isinstance(cut, dict) else None
        if kind not in KINDS:
            out.append(f"{key}: deployment.cuts gives it no kind of "
                       f"{sorted(KINDS)}")
            continue
        why = cut.get("why")
        if not isinstance(why, str) or not 1 <= len(why) <= 200:
            out.append(f"{key}: deployment.cuts gives it no `why` of at "
                       "most 200 characters")
        word, floor = KINDS[kind]
        if WIDTH.search(key) or word not in key:
            out.append(f"{key}: a cut of a width, which is never allowed "
                       f"(a {kind} key has {word!r} in its name)")
        elif run < floor(pub):
            out.append(f"{key}: {run} as run is under the floor of "
                       f"{floor(pub)} for {kind}")
    return out


def line(sizes: dict) -> str:
    """What a run's record says of the cut: ``cut: none``, or every cut key
    with its value as run, the published one and its kind."""
    if not sizes.get("reduced"):
        return "cut: none"
    dep = sizes["deployment"]
    parts = [f"{k} {sizes[k]} of {sizes['published'][k]} "
             f"({dep['cuts'][k]['kind']})" for k in sizes["reduced"]]
    return ("cut: " + ", ".join(parts) + "; one of "
            f"{dep['chips_sharing_a_layer']} chips that share a layer: "
            f"{dep['how']}")
