from keystone_tpu.nodes.learning.linear_mapper import (
    LinearMapEstimator,
    LinearMapper,
)
from keystone_tpu.nodes.learning.local_least_squares import (
    LocalLeastSquaresEstimator,
)
from keystone_tpu.nodes.learning.block_least_squares import (
    BlockLeastSquaresEstimator,
    BlockLinearMapper,
    BlockWeightedLeastSquaresEstimator,
)
from keystone_tpu.nodes.learning.least_squares import (
    LeastSquaresEstimator,
    SolverChoice,
    choose_solver,
)
from keystone_tpu.nodes.learning.pca import (
    DistributedPCAEstimator,
    PCAEstimator,
    PCATransformer,
)
from keystone_tpu.nodes.learning.zca import ZCAWhitener, ZCAWhitenerEstimator
from keystone_tpu.nodes.learning.kmeans import (
    KMeansModel,
    KMeansPlusPlusEstimator,
)
from keystone_tpu.nodes.learning.gmm import (
    GaussianMixtureModel,
    GaussianMixtureModelEstimator,
)
from keystone_tpu.nodes.learning.naive_bayes import (
    NaiveBayesEstimator,
    NaiveBayesModel,
)
from keystone_tpu.nodes.learning.logistic_regression import (
    LogisticRegressionEstimator,
    LogisticRegressionModel,
)
from keystone_tpu.nodes.learning.lda import LinearDiscriminantAnalysis
from keystone_tpu.nodes.learning.kernels import (
    GaussianKernelGenerator,
    KernelGenerator,
    LinearKernelGenerator,
)
from keystone_tpu.nodes.learning.kernel_ridge import (
    KernelBlockLinearMapper,
    KernelRidgeRegression,
)
from keystone_tpu.nodes.learning.kernel_ridge_cg import KernelRidgeCG

__all__ = [
    "LinearMapper",
    "LinearMapEstimator",
    "LocalLeastSquaresEstimator",
    "BlockLinearMapper",
    "BlockLeastSquaresEstimator",
    "BlockWeightedLeastSquaresEstimator",
    "LeastSquaresEstimator",
    "SolverChoice",
    "choose_solver",
    "PCAEstimator",
    "DistributedPCAEstimator",
    "PCATransformer",
    "ZCAWhitener",
    "ZCAWhitenerEstimator",
    "KMeansModel",
    "KMeansPlusPlusEstimator",
    "GaussianMixtureModel",
    "GaussianMixtureModelEstimator",
    "NaiveBayesModel",
    "NaiveBayesEstimator",
    "LogisticRegressionModel",
    "LogisticRegressionEstimator",
    "LinearDiscriminantAnalysis",
    "KernelGenerator",
    "GaussianKernelGenerator",
    "LinearKernelGenerator",
    "KernelRidgeRegression",
    "KernelRidgeCG",
    "KernelBlockLinearMapper",
]
