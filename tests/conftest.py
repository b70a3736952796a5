import sys
from pathlib import Path

# NOTE: deliberately no XLA_FLAGS here -- smoke tests and benches must see
# the single real CPU device.
SRC = Path(__file__).resolve().parent.parent / "src"
if str(SRC) not in sys.path:
    sys.path.insert(0, str(SRC))
