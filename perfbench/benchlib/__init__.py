"""Shared pieces of the chip benchmark: the yardstick that every cell uses.

Nothing here imports the program under test. `cells` finds configurations,
traffic mixes and metric readers by name; `gen` makes traffic from a seed;
`trace` captures and reduces a profiler trace; `peaks` holds the chips'
published peaks; `compare` holds the arithmetic that decides `correct`.
"""
