import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture
def device_defaults(monkeypatch):
    """Resolve the program's backends as on a TPU (the fleet megakernel,
    which off-TPU runs its host lowering), so the CPU drives the path the
    chip runs."""
    import repro.core.backends as backends

    monkeypatch.setattr(backends, "_on_tpu", lambda: True)
