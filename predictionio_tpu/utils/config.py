"""pio-env.sh loader (reference: conf/pio-env.sh sourced by bin/pio —
SURVEY.md §5 'Config/flag system': env / engine.json / CLI triple).

The reference's launcher sources a shell file exporting PIO_* variables.
``load_pio_env`` parses the same file format (export lines, simple
assignments, comments, ${VAR} interpolation) without spawning a shell and
merges it into the process env so ``StorageConfig.from_env`` sees it.
"""

from __future__ import annotations

import os
import re
from pathlib import Path
from typing import Dict, Optional

_ASSIGN = re.compile(r"^(?:export\s+)?([A-Za-z_][A-Za-z0-9_]*)=(.*)$")
_REF = re.compile(r"\$\{?([A-Za-z_][A-Za-z0-9_]*)\}?")


def load_pio_env(
    path: Optional[str] = None,
    apply: bool = True,
    base: Optional[Dict[str, str]] = None,
) -> Dict[str, str]:
    """Parse a pio-env.sh-style file; returns the variables it defines.

    Search order when path is None: $PIO_ENV_FILE, ./conf/pio-env.sh,
    ~/.pio/pio-env.sh.  Missing file → empty dict (defaults apply).
    """
    candidates = (
        [path]
        if path
        else [
            os.environ.get("PIO_ENV_FILE"),
            "conf/pio-env.sh",
            str(Path.home() / ".pio" / "pio-env.sh"),
        ]
    )
    found = next((c for c in candidates if c and Path(c).exists()), None)
    if found is None:
        return {}
    env: Dict[str, str] = dict(base if base is not None else os.environ)
    out: Dict[str, str] = {}
    for raw in Path(found).read_text().splitlines():
        line = raw.strip()
        if not line or line.startswith("#"):
            continue
        m = _ASSIGN.match(line)
        if not m:
            continue
        name, value = m.group(1), m.group(2).strip()
        single_quoted = len(value) >= 2 and value[0] == value[-1] == "'"
        if value and value[0] == value[-1] and value[0] in "\"'" and len(value) >= 2:
            value = value[1:-1]
        if not single_quoted:
            # shell `source` semantics: no ${VAR} expansion inside 'single quotes'
            value = _REF.sub(lambda mm: env.get(mm.group(1), ""), value)
        env[name] = value
        out[name] = value
    if apply:
        os.environ.update(out)
    return out


# The one compile-cache location this program ever chooses: a fixed path
# inside the checkout (listed in .gitignore).  Never the home directory,
# a temp name, a pid or a time — every `pio` process of a checkout must
# find what the previous one compiled.
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compilation_cache() -> None:
    """Persistent XLA compilation cache shared across pio processes.

    Every `pio train` / `pio deploy` is a fresh process; without a cache
    on disk the big CCO/ALS programs recompile on each run, and compiling
    is most of a cold train at a 100k-item catalog (PERF.md "Bring-up on
    TPU v5e").  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
    itself and this sets no directory at all — whoever runs the program
    places the cache.  Where it is not, the cache lives at
    ``COMPILE_CACHE_DIR``.  Thresholds are JAX's own
    (``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS``, ...).  Every entry
    point that compiles calls this before its first compile.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    try:
        COMPILE_CACHE_DIR.mkdir(parents=True, exist_ok=True)
    except OSError as e:  # a cache is an optimization, never a hard failure
        import logging

        logging.getLogger("pio.config").warning(
            "persistent XLA cache unavailable at %s: %s",
            COMPILE_CACHE_DIR, e)
        return
    import jax

    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
