"""Mean host part of a rack cell replan, the benchmark's replan span less its
solve and arbitration walls: ``host_ms.replan``'s reading."""

from same_reading import reader

read = reader("host_ms.replan")
