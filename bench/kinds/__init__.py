"""The kinds of cell, one module each, named by a mix's ``kind``.

Importing the package gives a multi-chip cell its reference
(``place.install``): the reference's weights split over the cell's chips
instead of lying whole on the first, and first steps that hold less of
its state at once.  One-chip cells keep theirs.
"""
from . import place, train

place.install(train)
