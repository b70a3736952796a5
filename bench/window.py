"""Pure parts of the measured window: seeds and accounting.

Nothing here touches JAX or the program, so the tests check it on any host.
"""

from __future__ import annotations

from dataclasses import dataclass, field

WARMUP, WINDOW = 0, 1


def block_seed(run_seed: int, role: int, block: int, item: int) -> list[int]:
    """Entropy for one generated burst: the run's seed, the role
    (:data:`WARMUP` or :data:`WINDOW`), the block and the item in it.  The
    role keeps warm-up traffic apart from every window seed; the run's seed
    may be any whole number, larger than 32 bits too."""
    if run_seed < 0:
        raise ValueError(f"seed must be a whole number >= 0, got {run_seed}")
    return [int(run_seed), int(role), int(block), int(item)]


@dataclass
class Window:
    """Calls completed by the program's entry over the wall time spent
    inside those entry calls.  Generation between calls is kept apart."""

    calls: int = 0
    entry_s: float = 0.0
    generate_s: float = 0.0
    blocks: int = 0
    block_entry_s: list = field(default_factory=list)

    def add(self, calls: int, entry_s: float, generate_s: float = 0.0) -> None:
        if calls < 0 or entry_s <= 0.0:
            raise ValueError(f"a block needs calls >= 0 and time > 0, got "
                             f"{calls} calls in {entry_s!r} s")
        self.calls += calls
        self.entry_s += entry_s
        self.generate_s += generate_s
        self.blocks += 1
        self.block_entry_s.append(entry_s)

    @property
    def calls_per_s(self) -> float:
        if self.entry_s <= 0.0:
            raise ValueError("no block completed in the window")
        return self.calls / self.entry_s
