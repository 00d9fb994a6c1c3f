"""Device time per trip of the rack cell's batched solve, under scope
``jlcm.iterate`` (the projections under ``jlcm.project`` included), over the
recorded trips: ``solver_us_per_trip.replan``'s reading."""

from same_reading import reader

read = reader("solver_us_per_trip.replan")
