"""Generated traffic, kept beside the compile cache inside the checkout.

Keyed by configuration, traffic mix, seed, window length (where the
generator uses it) and the generator's version. The files hold only what
this benchmark wrote: bytes, numbers, strings, lists and dicts.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import time
from typing import Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CACHE_DIR = os.path.join(ROOT, ".bench_cache", "traffic")


def path_for(config: dict, traffic: dict, generator, seed: int, seconds: float) -> str:
    key = json.dumps(
        [config, traffic, generator.__name__, generator.VERSION, seed,
         seconds if generator.USES_SECONDS else None],
        sort_keys=True,
    )
    digest = hashlib.sha256(key.encode()).hexdigest()[:24]
    return os.path.join(
        CACHE_DIR, f"{config['name']}.{traffic['name']}.{seed}.{digest}.pkl"
    )


def load_or_build(
    config: dict, traffic: dict, generator, seed: int, seconds: float,
) -> Tuple[dict, dict]:
    """(data, how): `how` says whether it was built anew and how long
    either took; `setup_s` counts it whichever it was."""
    path = path_for(config, traffic, generator, seed, seconds)
    t0 = time.monotonic()
    if os.path.exists(path):
        with open(path, "rb") as f:
            data = pickle.load(f)
        return data, {"from_cache": True, "seconds": time.monotonic() - t0}
    data = generator.build(config, traffic, seed, seconds)
    os.makedirs(CACHE_DIR, exist_ok=True)
    tmp = f"{path}.{os.getpid()}.tmp"
    with open(tmp, "wb") as f:
        pickle.dump(data, f, protocol=pickle.HIGHEST_PROTOCOL)
    os.replace(tmp, path)
    return data, {"from_cache": False, "seconds": time.monotonic() - t0}
