"""The one traffic generator: it turns a mix file and a seed into requests.

A traffic mix is a JSON file under ``chipbench/traffic/``, found by its
name.  Its keys:

- ``loop``: ``"closed"``: a client sends the next group of requests when
  the previous group has been answered;
- ``group``: requests per group, e.g. the scans of one study or the
  prompts of one offline wave;
- ``prompt_lens`` and ``new_tokens`` (LM traffic): one prompt length per
  group, taken in this fixed cycle, and the tokens generated per request;
- the settings the client uses, read by the system's driver: ``batch``,
  ``sharded``.

Every seed gets the same work: the same groups in the same cycle.  The
seed chooses the content (which scan of the pool, in which order within
a group, which token ids), so that two seeds differ in inputs and not in
load.
"""
from __future__ import annotations

import dataclasses
import itertools
import json
from pathlib import Path
from typing import Any, Dict, Iterator, List, Optional

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    index: int                    # position in the run's request sequence
    group: int                    # which group
    seed: int                     # sub-seed for the request's content
    item: int                     # which entry of the input pool
    prompt_len: Optional[int] = None
    new_tokens: Optional[int] = None


def load_mix(root: Path, name: str) -> Dict[str, Any]:
    path = root / "traffic" / f"{name}.json"
    with open(path) as f:
        mix = json.load(f)
    if mix.get("loop") != "closed":
        raise ValueError(f"{path}: loop must be 'closed', got "
                         f"{mix.get('loop')!r}")
    return mix


def rng(seed: int, *stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream...) tuple."""
    return np.random.default_rng([seed % 2 ** 64, *stream])


def _sub_seed(seed: int, index: int) -> int:
    return int(rng(seed, 7, index).integers(0, 2 ** 63))


def closed_groups(mix: Dict[str, Any], seed: int, pool: int
                  ) -> Iterator[List[Request]]:
    """Endless groups of ``mix["group"]`` requests.  Each group visits the
    pool in a fresh seeded order; LM groups take their prompt length from
    the fixed ``prompt_lens`` cycle."""
    size = int(mix["group"])
    lens = mix.get("prompt_lens")
    index = 0
    for g in itertools.count():
        order = rng(seed, 1, g).permutation(max(pool, size))[:size] % pool
        plen = int(lens[g % len(lens)]) if lens else None
        group = []
        for item in order:
            group.append(Request(index=index, group=g,
                                 seed=_sub_seed(seed, index),
                                 item=int(item), prompt_len=plen,
                                 new_tokens=mix.get("new_tokens")))
            index += 1
        yield group
