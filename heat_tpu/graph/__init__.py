"""Graph algorithms (reference ``heat/graph/``)."""
from .laplacian import Laplacian, PairwiseSimilarity
