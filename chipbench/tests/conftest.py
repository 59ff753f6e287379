import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for path in (ROOT, ROOT / "src"):
    if str(path) not in sys.path:
        sys.path.insert(0, str(path))


@pytest.fixture
def no_compile_cache(monkeypatch):
    """Keep a test's compiles out of the checkout's persistent cache."""
    import repro.launch.serve as serve

    monkeypatch.setattr(serve, "use_compile_cache", lambda: "off")
