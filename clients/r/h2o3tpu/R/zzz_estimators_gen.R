# GENERATED by clients/bindings_gen.py --r — do not edit.
#
# One h2o.* verb per algorithm registered on the generating server, with
# EVERY builder parameter as an explicit formal whose default mirrors the
# server's ModelBuilders metadata (the h2o-bindings gen_R analog). File is
# named zzz_* so R's collation order sources it AFTER the hand-written
# h2o3tpu.R: these full-signature verbs override the minimal ones there.

.gen_train <- function(algo, x, y, training_frame, validation_frame,
                       model_id, params, defaults) {
  send <- list()
  for (k in names(params)) {
    v <- params[[k]]
    if (is.null(v)) next
    d <- defaults[[k]]
    # only ship values that differ from the server's own default — the
    # wire stays minimal and server-side evolution of defaults wins
    if (!is.null(d) && !is.list(v) && length(v) == 1 && length(d) == 1
        && !is.na(v == d) && v == d) next
    send[[k]] <- v
  }
  if (!is.null(model_id)) send$model_id <- model_id
  do.call(.train, c(list(algo = algo, x = x, y = y,
                         training_frame = training_frame,
                         validation_frame = validation_frame), send))
}


# POST /3/ModelBuilders/aggregator — full server parameter surface
h2o.aggregator <- function(training_frame, x = NULL, y = NULL,
    validation_frame = NULL,
    model_id = NULL,
    seed = -1,
    nfolds = 0,
    fold_assignment = "Modulo",
    fold_column = NULL,
    weights_column = NULL,
    ignored_columns = NULL,
    max_runtime_secs = 0.0,
    keep_cross_validation_predictions = 0,
    checkpoint = NULL,
    auto_recovery_dir = NULL,
    custom_metric_func = NULL,
    target_num_exemplars = 100,
    rel_tol_num_exemplars = 0.5,
    transform = "NORMALIZE") {
  .gen_train("aggregator", x, y, training_frame, validation_frame, model_id,
             params = list(seed = seed, nfolds = nfolds, fold_assignment = fold_assignment, fold_column = fold_column, weights_column = weights_column, ignored_columns = ignored_columns, max_runtime_secs = max_runtime_secs, keep_cross_validation_predictions = keep_cross_validation_predictions, checkpoint = checkpoint, auto_recovery_dir = auto_recovery_dir, custom_metric_func = custom_metric_func, target_num_exemplars = target_num_exemplars, rel_tol_num_exemplars = rel_tol_num_exemplars, transform = transform),
             defaults = list(seed = -1, nfolds = 0, fold_assignment = "Modulo", max_runtime_secs = 0.0, keep_cross_validation_predictions = 0, target_num_exemplars = 100, rel_tol_num_exemplars = 0.5, transform = "NORMALIZE"))
}


# POST /3/ModelBuilders/anovaglm — full server parameter surface
h2o.anovaglm <- function(x = NULL, y = NULL, training_frame,
    validation_frame = NULL,
    model_id = NULL,
    seed = -1,
    nfolds = 0,
    fold_assignment = "Modulo",
    fold_column = NULL,
    weights_column = NULL,
    ignored_columns = NULL,
    max_runtime_secs = 0.0,
    keep_cross_validation_predictions = 0,
    checkpoint = NULL,
    auto_recovery_dir = NULL,
    custom_metric_func = NULL,
    family = "AUTO",
    lambda_ = 0.0,
    alpha = 0.0,
    standardize = 1,
    highest_interaction_term = 2) {
  .gen_train("anovaglm", x, y, training_frame, validation_frame, model_id,
             params = list(seed = seed, nfolds = nfolds, fold_assignment = fold_assignment, fold_column = fold_column, weights_column = weights_column, ignored_columns = ignored_columns, max_runtime_secs = max_runtime_secs, keep_cross_validation_predictions = keep_cross_validation_predictions, checkpoint = checkpoint, auto_recovery_dir = auto_recovery_dir, custom_metric_func = custom_metric_func, family = family, lambda_ = lambda_, alpha = alpha, standardize = standardize, highest_interaction_term = highest_interaction_term),
             defaults = list(seed = -1, nfolds = 0, fold_assignment = "Modulo", max_runtime_secs = 0.0, keep_cross_validation_predictions = 0, family = "AUTO", lambda_ = 0.0, alpha = 0.0, standardize = 1, highest_interaction_term = 2))
}


# POST /3/ModelBuilders/coxph — full server parameter surface
h2o.coxph <- function(x = NULL, y = NULL, training_frame,
    validation_frame = NULL,
    model_id = NULL,
    event_column = NULL,
    seed = -1,
    nfolds = 0,
    fold_assignment = "Modulo",
    fold_column = NULL,
    weights_column = NULL,
    ignored_columns = NULL,
    max_runtime_secs = 0.0,
    keep_cross_validation_predictions = 0,
    checkpoint = NULL,
    auto_recovery_dir = NULL,
    custom_metric_func = NULL,
    stop_column = NULL,
    ties = "efron",
    max_iterations = 20,
    lre = 9.0) {
  if (is.null(y) && !is.null(event_column)) y <- event_column
  .gen_train("coxph", x, y, training_frame, validation_frame, model_id,
             params = list(seed = seed, nfolds = nfolds, fold_assignment = fold_assignment, fold_column = fold_column, weights_column = weights_column, ignored_columns = ignored_columns, max_runtime_secs = max_runtime_secs, keep_cross_validation_predictions = keep_cross_validation_predictions, checkpoint = checkpoint, auto_recovery_dir = auto_recovery_dir, custom_metric_func = custom_metric_func, stop_column = stop_column, ties = ties, max_iterations = max_iterations, lre = lre),
             defaults = list(seed = -1, nfolds = 0, fold_assignment = "Modulo", max_runtime_secs = 0.0, keep_cross_validation_predictions = 0, ties = "efron", max_iterations = 20, lre = 9.0))
}


# POST /3/ModelBuilders/decisiontree — full server parameter surface
h2o.decision_tree <- function(x = NULL, y = NULL, training_frame,
    validation_frame = NULL,
    model_id = NULL,
    seed = -1,
    nfolds = 0,
    fold_assignment = "Modulo",
    fold_column = NULL,
    weights_column = NULL,
    ignored_columns = NULL,
    max_runtime_secs = 0.0,
    keep_cross_validation_predictions = 0,
    checkpoint = NULL,
    auto_recovery_dir = NULL,
    custom_metric_func = NULL,
    ntrees = 1,
    max_depth = 10,
    min_rows = 10.0,
    nbins = 64,
    sample_rate = 1.0,
    col_sample_rate_per_tree = 1.0,
    min_split_improvement = 1e-05,
    stopping_rounds = 0,
    stopping_metric = "AUTO",
    stopping_tolerance = 0.001,
    score_tree_interval = 0,
    score_each_iteration = 0,
    monotone_constraints = NULL,
    interaction_constraints = NULL,
    calibrate_model = 0,
    calibration_frame = NULL,
    calibration_method = "PlattScaling",
    nbins_cats = 1024,
    categorical_encoding = "AUTO",
    offset_column = NULL) {
  .gen_train("decisiontree", x, y, training_frame, validation_frame, model_id,
             params = list(seed = seed, nfolds = nfolds, fold_assignment = fold_assignment, fold_column = fold_column, weights_column = weights_column, ignored_columns = ignored_columns, max_runtime_secs = max_runtime_secs, keep_cross_validation_predictions = keep_cross_validation_predictions, checkpoint = checkpoint, auto_recovery_dir = auto_recovery_dir, custom_metric_func = custom_metric_func, ntrees = ntrees, max_depth = max_depth, min_rows = min_rows, nbins = nbins, sample_rate = sample_rate, col_sample_rate_per_tree = col_sample_rate_per_tree, min_split_improvement = min_split_improvement, stopping_rounds = stopping_rounds, stopping_metric = stopping_metric, stopping_tolerance = stopping_tolerance, score_tree_interval = score_tree_interval, score_each_iteration = score_each_iteration, monotone_constraints = monotone_constraints, interaction_constraints = interaction_constraints, calibrate_model = calibrate_model, calibration_frame = calibration_frame, calibration_method = calibration_method, nbins_cats = nbins_cats, categorical_encoding = categorical_encoding, offset_column = offset_column),
             defaults = list(seed = -1, nfolds = 0, fold_assignment = "Modulo", max_runtime_secs = 0.0, keep_cross_validation_predictions = 0, ntrees = 1, max_depth = 10, min_rows = 10.0, nbins = 64, sample_rate = 1.0, col_sample_rate_per_tree = 1.0, min_split_improvement = 1e-05, stopping_rounds = 0, stopping_metric = "AUTO", stopping_tolerance = 0.001, score_tree_interval = 0, score_each_iteration = 0, calibrate_model = 0, calibration_method = "PlattScaling", nbins_cats = 1024, categorical_encoding = "AUTO"))
}


# POST /3/ModelBuilders/deeplearning — full server parameter surface
h2o.deeplearning <- function(x = NULL, y = NULL, training_frame,
    validation_frame = NULL,
    model_id = NULL,
    seed = -1,
    nfolds = 0,
    fold_assignment = "Modulo",
    fold_column = NULL,
    weights_column = NULL,
    ignored_columns = NULL,
    max_runtime_secs = 0.0,
    keep_cross_validation_predictions = 0,
    checkpoint = NULL,
    auto_recovery_dir = NULL,
    custom_metric_func = NULL,
    hidden = c(200, 200),
    epochs = 10.0,
    activation = "Rectifier",
    adaptive_rate = 1,
    rho = 0.99,
    epsilon = 1e-08,
    rate = 0.005,
    rate_annealing = 1e-06,
    rate_decay = 1.0,
    momentum_start = 0.0,
    momentum_ramp = 1000000.0,
    momentum_stable = 0.0,
    nesterov_accelerated_gradient = 1,
    input_dropout_ratio = 0.0,
    hidden_dropout_ratios = NULL,
    l1 = 0.0,
    l2 = 0.0,
    max_w2 = 3.4028235e+38,
    loss = "Automatic",
    huber_alpha = 0.9,
    mini_batch_size = 32,
    standardize = 1,
    use_all_factor_levels = 1,
    initial_weight_distribution = "UniformAdaptive",
    initial_weight_scale = 1.0,
    autoencoder = 0,
    score_each_iteration = 0,
    train_samples_per_iteration = -2,
    classification_stop = 0.0,
    ignore_const_cols = 1,
    elastic = 0,
    local_steps = 1) {
  .gen_train("deeplearning", x, y, training_frame, validation_frame, model_id,
             params = list(seed = seed, nfolds = nfolds, fold_assignment = fold_assignment, fold_column = fold_column, weights_column = weights_column, ignored_columns = ignored_columns, max_runtime_secs = max_runtime_secs, keep_cross_validation_predictions = keep_cross_validation_predictions, checkpoint = checkpoint, auto_recovery_dir = auto_recovery_dir, custom_metric_func = custom_metric_func, hidden = hidden, epochs = epochs, activation = activation, adaptive_rate = adaptive_rate, rho = rho, epsilon = epsilon, rate = rate, rate_annealing = rate_annealing, rate_decay = rate_decay, momentum_start = momentum_start, momentum_ramp = momentum_ramp, momentum_stable = momentum_stable, nesterov_accelerated_gradient = nesterov_accelerated_gradient, input_dropout_ratio = input_dropout_ratio, hidden_dropout_ratios = hidden_dropout_ratios, l1 = l1, l2 = l2, max_w2 = max_w2, loss = loss, huber_alpha = huber_alpha, mini_batch_size = mini_batch_size, standardize = standardize, use_all_factor_levels = use_all_factor_levels, initial_weight_distribution = initial_weight_distribution, initial_weight_scale = initial_weight_scale, autoencoder = autoencoder, score_each_iteration = score_each_iteration, train_samples_per_iteration = train_samples_per_iteration, classification_stop = classification_stop, ignore_const_cols = ignore_const_cols, elastic = elastic, local_steps = local_steps),
             defaults = list(seed = -1, nfolds = 0, fold_assignment = "Modulo", max_runtime_secs = 0.0, keep_cross_validation_predictions = 0, hidden = c(200, 200), epochs = 10.0, activation = "Rectifier", adaptive_rate = 1, rho = 0.99, epsilon = 1e-08, rate = 0.005, rate_annealing = 1e-06, rate_decay = 1.0, momentum_start = 0.0, momentum_ramp = 1000000.0, momentum_stable = 0.0, nesterov_accelerated_gradient = 1, input_dropout_ratio = 0.0, l1 = 0.0, l2 = 0.0, max_w2 = 3.4028235e+38, loss = "Automatic", huber_alpha = 0.9, mini_batch_size = 32, standardize = 1, use_all_factor_levels = 1, initial_weight_distribution = "UniformAdaptive", initial_weight_scale = 1.0, autoencoder = 0, score_each_iteration = 0, train_samples_per_iteration = -2, classification_stop = 0.0, ignore_const_cols = 1, elastic = 0, local_steps = 1))
}


# POST /3/ModelBuilders/drf — full server parameter surface
h2o.randomForest <- function(x = NULL, y = NULL, training_frame,
    validation_frame = NULL,
    model_id = NULL,
    seed = -1,
    nfolds = 0,
    fold_assignment = "Modulo",
    fold_column = NULL,
    weights_column = NULL,
    ignored_columns = NULL,
    max_runtime_secs = 0.0,
    keep_cross_validation_predictions = 0,
    checkpoint = NULL,
    auto_recovery_dir = NULL,
    custom_metric_func = NULL,
    ntrees = 50,
    max_depth = 14,
    min_rows = 1.0,
    nbins = 64,
    sample_rate = 0.632,
    col_sample_rate_per_tree = 1.0,
    min_split_improvement = 1e-05,
    stopping_rounds = 0,
    stopping_metric = "AUTO",
    stopping_tolerance = 0.001,
    score_tree_interval = 0,
    score_each_iteration = 0,
    monotone_constraints = NULL,
    interaction_constraints = NULL,
    calibrate_model = 0,
    calibration_frame = NULL,
    calibration_method = "PlattScaling",
    nbins_cats = 1024,
    categorical_encoding = "AUTO",
    offset_column = NULL,
    mtries = -1,
    binomial_double_trees = 0) {
  .gen_train("drf", x, y, training_frame, validation_frame, model_id,
             params = list(seed = seed, nfolds = nfolds, fold_assignment = fold_assignment, fold_column = fold_column, weights_column = weights_column, ignored_columns = ignored_columns, max_runtime_secs = max_runtime_secs, keep_cross_validation_predictions = keep_cross_validation_predictions, checkpoint = checkpoint, auto_recovery_dir = auto_recovery_dir, custom_metric_func = custom_metric_func, ntrees = ntrees, max_depth = max_depth, min_rows = min_rows, nbins = nbins, sample_rate = sample_rate, col_sample_rate_per_tree = col_sample_rate_per_tree, min_split_improvement = min_split_improvement, stopping_rounds = stopping_rounds, stopping_metric = stopping_metric, stopping_tolerance = stopping_tolerance, score_tree_interval = score_tree_interval, score_each_iteration = score_each_iteration, monotone_constraints = monotone_constraints, interaction_constraints = interaction_constraints, calibrate_model = calibrate_model, calibration_frame = calibration_frame, calibration_method = calibration_method, nbins_cats = nbins_cats, categorical_encoding = categorical_encoding, offset_column = offset_column, mtries = mtries, binomial_double_trees = binomial_double_trees),
             defaults = list(seed = -1, nfolds = 0, fold_assignment = "Modulo", max_runtime_secs = 0.0, keep_cross_validation_predictions = 0, ntrees = 50, max_depth = 14, min_rows = 1.0, nbins = 64, sample_rate = 0.632, col_sample_rate_per_tree = 1.0, min_split_improvement = 1e-05, stopping_rounds = 0, stopping_metric = "AUTO", stopping_tolerance = 0.001, score_tree_interval = 0, score_each_iteration = 0, calibrate_model = 0, calibration_method = "PlattScaling", nbins_cats = 1024, categorical_encoding = "AUTO", mtries = -1, binomial_double_trees = 0))
}


# POST /3/ModelBuilders/extendedisolationforest — full server parameter surface
h2o.extendedIsolationForest <- function(training_frame, x = NULL, y = NULL,
    validation_frame = NULL,
    model_id = NULL,
    seed = -1,
    nfolds = 0,
    fold_assignment = "Modulo",
    fold_column = NULL,
    weights_column = NULL,
    ignored_columns = NULL,
    max_runtime_secs = 0.0,
    keep_cross_validation_predictions = 0,
    checkpoint = NULL,
    auto_recovery_dir = NULL,
    custom_metric_func = NULL,
    ntrees = 100,
    sample_size = 256,
    extension_level = 0) {
  .gen_train("extendedisolationforest", x, y, training_frame, validation_frame, model_id,
             params = list(seed = seed, nfolds = nfolds, fold_assignment = fold_assignment, fold_column = fold_column, weights_column = weights_column, ignored_columns = ignored_columns, max_runtime_secs = max_runtime_secs, keep_cross_validation_predictions = keep_cross_validation_predictions, checkpoint = checkpoint, auto_recovery_dir = auto_recovery_dir, custom_metric_func = custom_metric_func, ntrees = ntrees, sample_size = sample_size, extension_level = extension_level),
             defaults = list(seed = -1, nfolds = 0, fold_assignment = "Modulo", max_runtime_secs = 0.0, keep_cross_validation_predictions = 0, ntrees = 100, sample_size = 256, extension_level = 0))
}


# POST /3/ModelBuilders/gam — full server parameter surface
h2o.gam <- function(x = NULL, y = NULL, training_frame,
    validation_frame = NULL,
    model_id = NULL,
    seed = -1,
    nfolds = 0,
    fold_assignment = "Modulo",
    fold_column = NULL,
    weights_column = NULL,
    ignored_columns = NULL,
    max_runtime_secs = 0.0,
    keep_cross_validation_predictions = 0,
    checkpoint = NULL,
    auto_recovery_dir = NULL,
    custom_metric_func = NULL,
    gam_columns = NULL,
    bs = NULL,
    num_knots = 5,
    knot_ids = NULL,
    splines_non_negative = 1,
    family = "AUTO",
    lambda_ = 0.0,
    alpha = 0.0,
    scale = 0.0001,
    standardize = 1,
    max_iterations = 50) {
  if (!is.null(gam_columns)) gam_columns <- as.list(gam_columns)
  .gen_train("gam", x, y, training_frame, validation_frame, model_id,
             params = list(seed = seed, nfolds = nfolds, fold_assignment = fold_assignment, fold_column = fold_column, weights_column = weights_column, ignored_columns = ignored_columns, max_runtime_secs = max_runtime_secs, keep_cross_validation_predictions = keep_cross_validation_predictions, checkpoint = checkpoint, auto_recovery_dir = auto_recovery_dir, custom_metric_func = custom_metric_func, gam_columns = gam_columns, bs = bs, num_knots = num_knots, knot_ids = knot_ids, splines_non_negative = splines_non_negative, family = family, lambda_ = lambda_, alpha = alpha, scale = scale, standardize = standardize, max_iterations = max_iterations),
             defaults = list(seed = -1, nfolds = 0, fold_assignment = "Modulo", max_runtime_secs = 0.0, keep_cross_validation_predictions = 0, num_knots = 5, splines_non_negative = 1, family = "AUTO", lambda_ = 0.0, alpha = 0.0, scale = 0.0001, standardize = 1, max_iterations = 50))
}


# POST /3/ModelBuilders/gbm — full server parameter surface
h2o.gbm <- function(x = NULL, y = NULL, training_frame,
    validation_frame = NULL,
    model_id = NULL,
    seed = -1,
    nfolds = 0,
    fold_assignment = "Modulo",
    fold_column = NULL,
    weights_column = NULL,
    ignored_columns = NULL,
    max_runtime_secs = 0.0,
    keep_cross_validation_predictions = 0,
    checkpoint = NULL,
    auto_recovery_dir = NULL,
    custom_metric_func = NULL,
    ntrees = 50,
    max_depth = 5,
    min_rows = 10.0,
    nbins = 64,
    sample_rate = 1.0,
    col_sample_rate_per_tree = 1.0,
    min_split_improvement = 1e-05,
    stopping_rounds = 0,
    stopping_metric = "AUTO",
    stopping_tolerance = 0.001,
    score_tree_interval = 0,
    score_each_iteration = 0,
    monotone_constraints = NULL,
    interaction_constraints = NULL,
    calibrate_model = 0,
    calibration_frame = NULL,
    calibration_method = "PlattScaling",
    nbins_cats = 1024,
    categorical_encoding = "AUTO",
    offset_column = NULL,
    learn_rate = 0.1,
    distribution = "AUTO",
    reg_lambda = 0.0,
    col_sample_rate = 1.0,
    quantile_alpha = 0.5,
    huber_alpha = 0.9,
    tweedie_power = 1.5,
    custom_distribution_func = NULL,
    trees_per_dispatch = 0) {
  .gen_train("gbm", x, y, training_frame, validation_frame, model_id,
             params = list(seed = seed, nfolds = nfolds, fold_assignment = fold_assignment, fold_column = fold_column, weights_column = weights_column, ignored_columns = ignored_columns, max_runtime_secs = max_runtime_secs, keep_cross_validation_predictions = keep_cross_validation_predictions, checkpoint = checkpoint, auto_recovery_dir = auto_recovery_dir, custom_metric_func = custom_metric_func, ntrees = ntrees, max_depth = max_depth, min_rows = min_rows, nbins = nbins, sample_rate = sample_rate, col_sample_rate_per_tree = col_sample_rate_per_tree, min_split_improvement = min_split_improvement, stopping_rounds = stopping_rounds, stopping_metric = stopping_metric, stopping_tolerance = stopping_tolerance, score_tree_interval = score_tree_interval, score_each_iteration = score_each_iteration, monotone_constraints = monotone_constraints, interaction_constraints = interaction_constraints, calibrate_model = calibrate_model, calibration_frame = calibration_frame, calibration_method = calibration_method, nbins_cats = nbins_cats, categorical_encoding = categorical_encoding, offset_column = offset_column, learn_rate = learn_rate, distribution = distribution, reg_lambda = reg_lambda, col_sample_rate = col_sample_rate, quantile_alpha = quantile_alpha, huber_alpha = huber_alpha, tweedie_power = tweedie_power, custom_distribution_func = custom_distribution_func, trees_per_dispatch = trees_per_dispatch),
             defaults = list(seed = -1, nfolds = 0, fold_assignment = "Modulo", max_runtime_secs = 0.0, keep_cross_validation_predictions = 0, ntrees = 50, max_depth = 5, min_rows = 10.0, nbins = 64, sample_rate = 1.0, col_sample_rate_per_tree = 1.0, min_split_improvement = 1e-05, stopping_rounds = 0, stopping_metric = "AUTO", stopping_tolerance = 0.001, score_tree_interval = 0, score_each_iteration = 0, calibrate_model = 0, calibration_method = "PlattScaling", nbins_cats = 1024, categorical_encoding = "AUTO", learn_rate = 0.1, distribution = "AUTO", reg_lambda = 0.0, col_sample_rate = 1.0, quantile_alpha = 0.5, huber_alpha = 0.9, tweedie_power = 1.5, trees_per_dispatch = 0))
}


# POST /3/ModelBuilders/glm — full server parameter surface
h2o.glm <- function(x = NULL, y = NULL, training_frame,
    validation_frame = NULL,
    model_id = NULL,
    seed = -1,
    nfolds = 0,
    fold_assignment = "Modulo",
    fold_column = NULL,
    weights_column = NULL,
    ignored_columns = NULL,
    max_runtime_secs = 0.0,
    keep_cross_validation_predictions = 0,
    checkpoint = NULL,
    auto_recovery_dir = NULL,
    custom_metric_func = NULL,
    family = "gaussian",
    solver = "IRLSM",
    alpha = 0.0,
    lambda_ = 0.0,
    tweedie_variance_power = 1.5,
    theta = 1.0,
    standardize = 1,
    use_all_factor_levels = 0,
    intercept = 1,
    non_negative = 0,
    max_iterations = 50,
    beta_epsilon = 0.0001,
    objective_epsilon = 1e-06,
    compute_p_values = 0,
    lambda_search = 0,
    nlambdas = 30,
    lambda_min_ratio = 0.0001,
    beta_constraints = NULL,
    offset_column = NULL,
    interactions = NULL,
    missing_values_handling = "MeanImputation",
    plug_values = NULL) {
  .gen_train("glm", x, y, training_frame, validation_frame, model_id,
             params = list(seed = seed, nfolds = nfolds, fold_assignment = fold_assignment, fold_column = fold_column, weights_column = weights_column, ignored_columns = ignored_columns, max_runtime_secs = max_runtime_secs, keep_cross_validation_predictions = keep_cross_validation_predictions, checkpoint = checkpoint, auto_recovery_dir = auto_recovery_dir, custom_metric_func = custom_metric_func, family = family, solver = solver, alpha = alpha, lambda_ = lambda_, tweedie_variance_power = tweedie_variance_power, theta = theta, standardize = standardize, use_all_factor_levels = use_all_factor_levels, intercept = intercept, non_negative = non_negative, max_iterations = max_iterations, beta_epsilon = beta_epsilon, objective_epsilon = objective_epsilon, compute_p_values = compute_p_values, lambda_search = lambda_search, nlambdas = nlambdas, lambda_min_ratio = lambda_min_ratio, beta_constraints = beta_constraints, offset_column = offset_column, interactions = interactions, missing_values_handling = missing_values_handling, plug_values = plug_values),
             defaults = list(seed = -1, nfolds = 0, fold_assignment = "Modulo", max_runtime_secs = 0.0, keep_cross_validation_predictions = 0, family = "gaussian", solver = "IRLSM", alpha = 0.0, lambda_ = 0.0, tweedie_variance_power = 1.5, theta = 1.0, standardize = 1, use_all_factor_levels = 0, intercept = 1, non_negative = 0, max_iterations = 50, beta_epsilon = 0.0001, objective_epsilon = 1e-06, compute_p_values = 0, lambda_search = 0, nlambdas = 30, lambda_min_ratio = 0.0001, missing_values_handling = "MeanImputation"))
}


# POST /3/ModelBuilders/glrm — full server parameter surface
h2o.glrm <- function(training_frame, x = NULL, y = NULL,
    validation_frame = NULL,
    model_id = NULL,
    seed = -1,
    nfolds = 0,
    fold_assignment = "Modulo",
    fold_column = NULL,
    weights_column = NULL,
    ignored_columns = NULL,
    max_runtime_secs = 0.0,
    keep_cross_validation_predictions = 0,
    checkpoint = NULL,
    auto_recovery_dir = NULL,
    custom_metric_func = NULL,
    k = 1,
    transform = "NONE",
    loss = "Quadratic",
    multi_loss = "Categorical",
    loss_by_col = NULL,
    loss_by_col_idx = NULL,
    period = 1.0,
    regularization_x = "None",
    regularization_y = "None",
    gamma_x = 0.0,
    gamma_y = 0.0,
    max_iterations = 100,
    init = "SVD") {
  .gen_train("glrm", x, y, training_frame, validation_frame, model_id,
             params = list(seed = seed, nfolds = nfolds, fold_assignment = fold_assignment, fold_column = fold_column, weights_column = weights_column, ignored_columns = ignored_columns, max_runtime_secs = max_runtime_secs, keep_cross_validation_predictions = keep_cross_validation_predictions, checkpoint = checkpoint, auto_recovery_dir = auto_recovery_dir, custom_metric_func = custom_metric_func, k = k, transform = transform, loss = loss, multi_loss = multi_loss, loss_by_col = loss_by_col, loss_by_col_idx = loss_by_col_idx, period = period, regularization_x = regularization_x, regularization_y = regularization_y, gamma_x = gamma_x, gamma_y = gamma_y, max_iterations = max_iterations, init = init),
             defaults = list(seed = -1, nfolds = 0, fold_assignment = "Modulo", max_runtime_secs = 0.0, keep_cross_validation_predictions = 0, k = 1, transform = "NONE", loss = "Quadratic", multi_loss = "Categorical", period = 1.0, regularization_x = "None", regularization_y = "None", gamma_x = 0.0, gamma_y = 0.0, max_iterations = 100, init = "SVD"))
}


# POST /3/ModelBuilders/grep — full server parameter surface
h2o.grep <- function(training_frame, x = NULL, y = NULL,
    validation_frame = NULL,
    model_id = NULL,
    seed = -1,
    nfolds = 0,
    fold_assignment = "Modulo",
    fold_column = NULL,
    weights_column = NULL,
    ignored_columns = NULL,
    max_runtime_secs = 0.0,
    keep_cross_validation_predictions = 0,
    checkpoint = NULL,
    auto_recovery_dir = NULL,
    custom_metric_func = NULL,
    regex = ".*") {
  .gen_train("grep", x, y, training_frame, validation_frame, model_id,
             params = list(seed = seed, nfolds = nfolds, fold_assignment = fold_assignment, fold_column = fold_column, weights_column = weights_column, ignored_columns = ignored_columns, max_runtime_secs = max_runtime_secs, keep_cross_validation_predictions = keep_cross_validation_predictions, checkpoint = checkpoint, auto_recovery_dir = auto_recovery_dir, custom_metric_func = custom_metric_func, regex = regex),
             defaults = list(seed = -1, nfolds = 0, fold_assignment = "Modulo", max_runtime_secs = 0.0, keep_cross_validation_predictions = 0, regex = ".*"))
}


# POST /3/ModelBuilders/hglm — full server parameter surface
h2o.hglm <- function(x = NULL, y = NULL, training_frame,
    validation_frame = NULL,
    model_id = NULL,
    seed = -1,
    nfolds = 0,
    fold_assignment = "Modulo",
    fold_column = NULL,
    weights_column = NULL,
    ignored_columns = NULL,
    max_runtime_secs = 0.0,
    keep_cross_validation_predictions = 0,
    checkpoint = NULL,
    auto_recovery_dir = NULL,
    custom_metric_func = NULL,
    group_column = NULL,
    random_columns = NULL,
    max_iterations = 50,
    em_epsilon = 1e-05) {
  .gen_train("hglm", x, y, training_frame, validation_frame, model_id,
             params = list(seed = seed, nfolds = nfolds, fold_assignment = fold_assignment, fold_column = fold_column, weights_column = weights_column, ignored_columns = ignored_columns, max_runtime_secs = max_runtime_secs, keep_cross_validation_predictions = keep_cross_validation_predictions, checkpoint = checkpoint, auto_recovery_dir = auto_recovery_dir, custom_metric_func = custom_metric_func, group_column = group_column, random_columns = random_columns, max_iterations = max_iterations, em_epsilon = em_epsilon),
             defaults = list(seed = -1, nfolds = 0, fold_assignment = "Modulo", max_runtime_secs = 0.0, keep_cross_validation_predictions = 0, max_iterations = 50, em_epsilon = 1e-05))
}


# POST /3/ModelBuilders/infogram — full server parameter surface
h2o.infogram <- function(x = NULL, y = NULL, training_frame,
    validation_frame = NULL,
    model_id = NULL,
    seed = -1,
    nfolds = 0,
    fold_assignment = "Modulo",
    fold_column = NULL,
    weights_column = NULL,
    ignored_columns = NULL,
    max_runtime_secs = 0.0,
    keep_cross_validation_predictions = 0,
    checkpoint = NULL,
    auto_recovery_dir = NULL,
    custom_metric_func = NULL,
    protected_columns = NULL,
    net_information_threshold = 0.1,
    total_information_threshold = 0.1,
    safety_index_threshold = 0.1,
    relevance_index_threshold = 0.1,
    top_n_features = 50,
    algorithm = "gbm",
    algorithm_params = NULL) {
  .gen_train("infogram", x, y, training_frame, validation_frame, model_id,
             params = list(seed = seed, nfolds = nfolds, fold_assignment = fold_assignment, fold_column = fold_column, weights_column = weights_column, ignored_columns = ignored_columns, max_runtime_secs = max_runtime_secs, keep_cross_validation_predictions = keep_cross_validation_predictions, checkpoint = checkpoint, auto_recovery_dir = auto_recovery_dir, custom_metric_func = custom_metric_func, protected_columns = protected_columns, net_information_threshold = net_information_threshold, total_information_threshold = total_information_threshold, safety_index_threshold = safety_index_threshold, relevance_index_threshold = relevance_index_threshold, top_n_features = top_n_features, algorithm = algorithm, algorithm_params = algorithm_params),
             defaults = list(seed = -1, nfolds = 0, fold_assignment = "Modulo", max_runtime_secs = 0.0, keep_cross_validation_predictions = 0, net_information_threshold = 0.1, total_information_threshold = 0.1, safety_index_threshold = 0.1, relevance_index_threshold = 0.1, top_n_features = 50, algorithm = "gbm"))
}


# POST /3/ModelBuilders/isolationforest — full server parameter surface
h2o.isolationForest <- function(training_frame, x = NULL, y = NULL,
    validation_frame = NULL,
    model_id = NULL,
    seed = -1,
    nfolds = 0,
    fold_assignment = "Modulo",
    fold_column = NULL,
    weights_column = NULL,
    ignored_columns = NULL,
    max_runtime_secs = 0.0,
    keep_cross_validation_predictions = 0,
    checkpoint = NULL,
    auto_recovery_dir = NULL,
    custom_metric_func = NULL,
    ntrees = 50,
    sample_size = 256,
    max_depth = 8) {
  .gen_train("isolationforest", x, y, training_frame, validation_frame, model_id,
             params = list(seed = seed, nfolds = nfolds, fold_assignment = fold_assignment, fold_column = fold_column, weights_column = weights_column, ignored_columns = ignored_columns, max_runtime_secs = max_runtime_secs, keep_cross_validation_predictions = keep_cross_validation_predictions, checkpoint = checkpoint, auto_recovery_dir = auto_recovery_dir, custom_metric_func = custom_metric_func, ntrees = ntrees, sample_size = sample_size, max_depth = max_depth),
             defaults = list(seed = -1, nfolds = 0, fold_assignment = "Modulo", max_runtime_secs = 0.0, keep_cross_validation_predictions = 0, ntrees = 50, sample_size = 256, max_depth = 8))
}


# POST /3/ModelBuilders/isotonicregression — full server parameter surface
h2o.isotonicregression <- function(x = NULL, y = NULL, training_frame,
    validation_frame = NULL,
    model_id = NULL,
    seed = -1,
    nfolds = 0,
    fold_assignment = "Modulo",
    fold_column = NULL,
    weights_column = NULL,
    ignored_columns = NULL,
    max_runtime_secs = 0.0,
    keep_cross_validation_predictions = 0,
    checkpoint = NULL,
    auto_recovery_dir = NULL,
    custom_metric_func = NULL,
    out_of_bounds = "NA") {
  .gen_train("isotonicregression", x, y, training_frame, validation_frame, model_id,
             params = list(seed = seed, nfolds = nfolds, fold_assignment = fold_assignment, fold_column = fold_column, weights_column = weights_column, ignored_columns = ignored_columns, max_runtime_secs = max_runtime_secs, keep_cross_validation_predictions = keep_cross_validation_predictions, checkpoint = checkpoint, auto_recovery_dir = auto_recovery_dir, custom_metric_func = custom_metric_func, out_of_bounds = out_of_bounds),
             defaults = list(seed = -1, nfolds = 0, fold_assignment = "Modulo", max_runtime_secs = 0.0, keep_cross_validation_predictions = 0, out_of_bounds = "NA"))
}


# POST /3/ModelBuilders/kmeans — full server parameter surface
h2o.kmeans <- function(training_frame, x = NULL, y = NULL,
    validation_frame = NULL,
    model_id = NULL,
    seed = -1,
    nfolds = 0,
    fold_assignment = "Modulo",
    fold_column = NULL,
    weights_column = NULL,
    ignored_columns = NULL,
    max_runtime_secs = 0.0,
    keep_cross_validation_predictions = 0,
    checkpoint = NULL,
    auto_recovery_dir = NULL,
    custom_metric_func = NULL,
    k = 1,
    max_iterations = 10,
    init = "Furthest",
    user_points = NULL,
    standardize = 1,
    estimate_k = 0) {
  .gen_train("kmeans", x, y, training_frame, validation_frame, model_id,
             params = list(seed = seed, nfolds = nfolds, fold_assignment = fold_assignment, fold_column = fold_column, weights_column = weights_column, ignored_columns = ignored_columns, max_runtime_secs = max_runtime_secs, keep_cross_validation_predictions = keep_cross_validation_predictions, checkpoint = checkpoint, auto_recovery_dir = auto_recovery_dir, custom_metric_func = custom_metric_func, k = k, max_iterations = max_iterations, init = init, user_points = user_points, standardize = standardize, estimate_k = estimate_k),
             defaults = list(seed = -1, nfolds = 0, fold_assignment = "Modulo", max_runtime_secs = 0.0, keep_cross_validation_predictions = 0, k = 1, max_iterations = 10, init = "Furthest", standardize = 1, estimate_k = 0))
}


# POST /3/ModelBuilders/modelselection — full server parameter surface
h2o.modelSelection <- function(x = NULL, y = NULL, training_frame,
    validation_frame = NULL,
    model_id = NULL,
    seed = -1,
    nfolds = 0,
    fold_assignment = "Modulo",
    fold_column = NULL,
    weights_column = NULL,
    ignored_columns = NULL,
    max_runtime_secs = 0.0,
    keep_cross_validation_predictions = 0,
    checkpoint = NULL,
    auto_recovery_dir = NULL,
    custom_metric_func = NULL,
    mode = "maxr",
    max_predictor_number = 3,
    min_predictor_number = 1,
    family = "AUTO",
    lambda_ = 0.0,
    alpha = 0.0,
    standardize = 1) {
  .gen_train("modelselection", x, y, training_frame, validation_frame, model_id,
             params = list(seed = seed, nfolds = nfolds, fold_assignment = fold_assignment, fold_column = fold_column, weights_column = weights_column, ignored_columns = ignored_columns, max_runtime_secs = max_runtime_secs, keep_cross_validation_predictions = keep_cross_validation_predictions, checkpoint = checkpoint, auto_recovery_dir = auto_recovery_dir, custom_metric_func = custom_metric_func, mode = mode, max_predictor_number = max_predictor_number, min_predictor_number = min_predictor_number, family = family, lambda_ = lambda_, alpha = alpha, standardize = standardize),
             defaults = list(seed = -1, nfolds = 0, fold_assignment = "Modulo", max_runtime_secs = 0.0, keep_cross_validation_predictions = 0, mode = "maxr", max_predictor_number = 3, min_predictor_number = 1, family = "AUTO", lambda_ = 0.0, alpha = 0.0, standardize = 1))
}


# POST /3/ModelBuilders/naivebayes — full server parameter surface
h2o.naiveBayes <- function(x = NULL, y = NULL, training_frame,
    validation_frame = NULL,
    model_id = NULL,
    seed = -1,
    nfolds = 0,
    fold_assignment = "Modulo",
    fold_column = NULL,
    weights_column = NULL,
    ignored_columns = NULL,
    max_runtime_secs = 0.0,
    keep_cross_validation_predictions = 0,
    checkpoint = NULL,
    auto_recovery_dir = NULL,
    custom_metric_func = NULL,
    laplace = 0.0,
    min_sdev = 0.001,
    eps_sdev = 0.0,
    min_prob = 0.001,
    eps_prob = 0.0) {
  .gen_train("naivebayes", x, y, training_frame, validation_frame, model_id,
             params = list(seed = seed, nfolds = nfolds, fold_assignment = fold_assignment, fold_column = fold_column, weights_column = weights_column, ignored_columns = ignored_columns, max_runtime_secs = max_runtime_secs, keep_cross_validation_predictions = keep_cross_validation_predictions, checkpoint = checkpoint, auto_recovery_dir = auto_recovery_dir, custom_metric_func = custom_metric_func, laplace = laplace, min_sdev = min_sdev, eps_sdev = eps_sdev, min_prob = min_prob, eps_prob = eps_prob),
             defaults = list(seed = -1, nfolds = 0, fold_assignment = "Modulo", max_runtime_secs = 0.0, keep_cross_validation_predictions = 0, laplace = 0.0, min_sdev = 0.001, eps_sdev = 0.0, min_prob = 0.001, eps_prob = 0.0))
}


# POST /3/ModelBuilders/pca — full server parameter surface
h2o.prcomp <- function(training_frame, x = NULL, y = NULL,
    validation_frame = NULL,
    model_id = NULL,
    seed = -1,
    nfolds = 0,
    fold_assignment = "Modulo",
    fold_column = NULL,
    weights_column = NULL,
    ignored_columns = NULL,
    max_runtime_secs = 0.0,
    keep_cross_validation_predictions = 0,
    checkpoint = NULL,
    auto_recovery_dir = NULL,
    custom_metric_func = NULL,
    k = 1,
    transform = "DEMEAN",
    pca_method = "GramSVD",
    use_all_factor_levels = 0,
    compute_metrics = 1,
    max_iterations = 1000) {
  .gen_train("pca", x, y, training_frame, validation_frame, model_id,
             params = list(seed = seed, nfolds = nfolds, fold_assignment = fold_assignment, fold_column = fold_column, weights_column = weights_column, ignored_columns = ignored_columns, max_runtime_secs = max_runtime_secs, keep_cross_validation_predictions = keep_cross_validation_predictions, checkpoint = checkpoint, auto_recovery_dir = auto_recovery_dir, custom_metric_func = custom_metric_func, k = k, transform = transform, pca_method = pca_method, use_all_factor_levels = use_all_factor_levels, compute_metrics = compute_metrics, max_iterations = max_iterations),
             defaults = list(seed = -1, nfolds = 0, fold_assignment = "Modulo", max_runtime_secs = 0.0, keep_cross_validation_predictions = 0, k = 1, transform = "DEMEAN", pca_method = "GramSVD", use_all_factor_levels = 0, compute_metrics = 1, max_iterations = 1000))
}


# POST /3/ModelBuilders/psvm — full server parameter surface
h2o.psvm <- function(x = NULL, y = NULL, training_frame,
    validation_frame = NULL,
    model_id = NULL,
    seed = -1,
    nfolds = 0,
    fold_assignment = "Modulo",
    fold_column = NULL,
    weights_column = NULL,
    ignored_columns = NULL,
    max_runtime_secs = 0.0,
    keep_cross_validation_predictions = 0,
    checkpoint = NULL,
    auto_recovery_dir = NULL,
    custom_metric_func = NULL,
    hyper_param = 1.0,
    positive_weight = 1.0,
    negative_weight = 1.0,
    kernel_type = "gaussian",
    gamma = -1.0,
    rank_ratio = -1.0,
    sv_threshold = 0.0001,
    max_iterations = 200,
    mu_factor = 10.0,
    feasible_threshold = 0.001,
    surrogate_gap_threshold = 0.001) {
  .gen_train("psvm", x, y, training_frame, validation_frame, model_id,
             params = list(seed = seed, nfolds = nfolds, fold_assignment = fold_assignment, fold_column = fold_column, weights_column = weights_column, ignored_columns = ignored_columns, max_runtime_secs = max_runtime_secs, keep_cross_validation_predictions = keep_cross_validation_predictions, checkpoint = checkpoint, auto_recovery_dir = auto_recovery_dir, custom_metric_func = custom_metric_func, hyper_param = hyper_param, positive_weight = positive_weight, negative_weight = negative_weight, kernel_type = kernel_type, gamma = gamma, rank_ratio = rank_ratio, sv_threshold = sv_threshold, max_iterations = max_iterations, mu_factor = mu_factor, feasible_threshold = feasible_threshold, surrogate_gap_threshold = surrogate_gap_threshold),
             defaults = list(seed = -1, nfolds = 0, fold_assignment = "Modulo", max_runtime_secs = 0.0, keep_cross_validation_predictions = 0, hyper_param = 1.0, positive_weight = 1.0, negative_weight = 1.0, kernel_type = "gaussian", gamma = -1.0, rank_ratio = -1.0, sv_threshold = 0.0001, max_iterations = 200, mu_factor = 10.0, feasible_threshold = 0.001, surrogate_gap_threshold = 0.001))
}


# POST /3/ModelBuilders/rulefit — full server parameter surface
h2o.rulefit <- function(x = NULL, y = NULL, training_frame,
    validation_frame = NULL,
    model_id = NULL,
    seed = -1,
    nfolds = 0,
    fold_assignment = "Modulo",
    fold_column = NULL,
    weights_column = NULL,
    ignored_columns = NULL,
    max_runtime_secs = 0.0,
    keep_cross_validation_predictions = 0,
    checkpoint = NULL,
    auto_recovery_dir = NULL,
    custom_metric_func = NULL,
    model_type = "rules_and_linear",
    min_rule_length = 1,
    max_rule_length = 3,
    rule_generation_ntrees = 10,
    lambda_ = 0.001,
    max_num_rules = -1) {
  .gen_train("rulefit", x, y, training_frame, validation_frame, model_id,
             params = list(seed = seed, nfolds = nfolds, fold_assignment = fold_assignment, fold_column = fold_column, weights_column = weights_column, ignored_columns = ignored_columns, max_runtime_secs = max_runtime_secs, keep_cross_validation_predictions = keep_cross_validation_predictions, checkpoint = checkpoint, auto_recovery_dir = auto_recovery_dir, custom_metric_func = custom_metric_func, model_type = model_type, min_rule_length = min_rule_length, max_rule_length = max_rule_length, rule_generation_ntrees = rule_generation_ntrees, lambda_ = lambda_, max_num_rules = max_num_rules),
             defaults = list(seed = -1, nfolds = 0, fold_assignment = "Modulo", max_runtime_secs = 0.0, keep_cross_validation_predictions = 0, model_type = "rules_and_linear", min_rule_length = 1, max_rule_length = 3, rule_generation_ntrees = 10, lambda_ = 0.001, max_num_rules = -1))
}


# POST /3/ModelBuilders/stackedensemble — full server parameter surface
h2o.stackedEnsemble <- function(x = NULL, y = NULL, training_frame,
    validation_frame = NULL,
    model_id = NULL,
    seed = -1,
    nfolds = 0,
    fold_assignment = "Modulo",
    fold_column = NULL,
    weights_column = NULL,
    ignored_columns = NULL,
    max_runtime_secs = 0.0,
    keep_cross_validation_predictions = 0,
    checkpoint = NULL,
    auto_recovery_dir = NULL,
    custom_metric_func = NULL,
    base_models = NULL,
    metalearner_algorithm = "AUTO",
    metalearner_params = NULL) {
  if (!is.null(base_models))
    base_models <- as.list(vapply(base_models, function(b)
      if (inherits(b, "H2OModel")) b$model_id else as.character(b),
      character(1)))
  .gen_train("stackedensemble", x, y, training_frame, validation_frame, model_id,
             params = list(seed = seed, nfolds = nfolds, fold_assignment = fold_assignment, fold_column = fold_column, weights_column = weights_column, ignored_columns = ignored_columns, max_runtime_secs = max_runtime_secs, keep_cross_validation_predictions = keep_cross_validation_predictions, checkpoint = checkpoint, auto_recovery_dir = auto_recovery_dir, custom_metric_func = custom_metric_func, base_models = base_models, metalearner_algorithm = metalearner_algorithm, metalearner_params = metalearner_params),
             defaults = list(seed = -1, nfolds = 0, fold_assignment = "Modulo", max_runtime_secs = 0.0, keep_cross_validation_predictions = 0, base_models = NULL, metalearner_algorithm = "AUTO"))
}


# POST /3/ModelBuilders/svd — full server parameter surface
h2o.svd <- function(training_frame, x = NULL, y = NULL,
    validation_frame = NULL,
    model_id = NULL,
    seed = -1,
    nfolds = 0,
    fold_assignment = "Modulo",
    fold_column = NULL,
    weights_column = NULL,
    ignored_columns = NULL,
    max_runtime_secs = 0.0,
    keep_cross_validation_predictions = 0,
    checkpoint = NULL,
    auto_recovery_dir = NULL,
    custom_metric_func = NULL,
    nv = 1,
    transform = "NONE",
    svd_method = "GramSVD",
    use_all_factor_levels = 1) {
  .gen_train("svd", x, y, training_frame, validation_frame, model_id,
             params = list(seed = seed, nfolds = nfolds, fold_assignment = fold_assignment, fold_column = fold_column, weights_column = weights_column, ignored_columns = ignored_columns, max_runtime_secs = max_runtime_secs, keep_cross_validation_predictions = keep_cross_validation_predictions, checkpoint = checkpoint, auto_recovery_dir = auto_recovery_dir, custom_metric_func = custom_metric_func, nv = nv, transform = transform, svd_method = svd_method, use_all_factor_levels = use_all_factor_levels),
             defaults = list(seed = -1, nfolds = 0, fold_assignment = "Modulo", max_runtime_secs = 0.0, keep_cross_validation_predictions = 0, nv = 1, transform = "NONE", svd_method = "GramSVD", use_all_factor_levels = 1))
}


# POST /3/ModelBuilders/targetencoder — full server parameter surface
h2o.targetencoder <- function(x = NULL, y = NULL, training_frame,
    validation_frame = NULL,
    model_id = NULL,
    seed = -1,
    nfolds = 0,
    fold_assignment = "Modulo",
    fold_column = NULL,
    weights_column = NULL,
    ignored_columns = NULL,
    max_runtime_secs = 0.0,
    keep_cross_validation_predictions = 0,
    checkpoint = NULL,
    auto_recovery_dir = NULL,
    custom_metric_func = NULL,
    columns = NULL,
    data_leakage_handling = "None",
    blending = 0,
    inflection_point = 10.0,
    smoothing = 20.0,
    noise = 0.0) {
  .gen_train("targetencoder", x, y, training_frame, validation_frame, model_id,
             params = list(seed = seed, nfolds = nfolds, fold_assignment = fold_assignment, fold_column = fold_column, weights_column = weights_column, ignored_columns = ignored_columns, max_runtime_secs = max_runtime_secs, keep_cross_validation_predictions = keep_cross_validation_predictions, checkpoint = checkpoint, auto_recovery_dir = auto_recovery_dir, custom_metric_func = custom_metric_func, columns = columns, data_leakage_handling = data_leakage_handling, blending = blending, inflection_point = inflection_point, smoothing = smoothing, noise = noise),
             defaults = list(seed = -1, nfolds = 0, fold_assignment = "Modulo", max_runtime_secs = 0.0, keep_cross_validation_predictions = 0, data_leakage_handling = "None", blending = 0, inflection_point = 10.0, smoothing = 20.0, noise = 0.0))
}


# POST /3/ModelBuilders/upliftdrf — full server parameter surface
h2o.upliftRandomForest <- function(x = NULL, y = NULL, training_frame,
    validation_frame = NULL,
    model_id = NULL,
    seed = -1,
    nfolds = 0,
    fold_assignment = "Modulo",
    fold_column = NULL,
    weights_column = NULL,
    ignored_columns = NULL,
    max_runtime_secs = 0.0,
    keep_cross_validation_predictions = 0,
    checkpoint = NULL,
    auto_recovery_dir = NULL,
    custom_metric_func = NULL,
    ntrees = 50,
    max_depth = 5,
    min_rows = 10.0,
    nbins = 64,
    sample_rate = 0.632,
    col_sample_rate_per_tree = 1.0,
    min_split_improvement = 1e-05,
    stopping_rounds = 0,
    stopping_metric = "AUTO",
    stopping_tolerance = 0.001,
    score_tree_interval = 0,
    score_each_iteration = 0,
    monotone_constraints = NULL,
    interaction_constraints = NULL,
    calibrate_model = 0,
    calibration_frame = NULL,
    calibration_method = "PlattScaling",
    nbins_cats = 1024,
    categorical_encoding = "AUTO",
    offset_column = NULL,
    treatment_column = NULL,
    uplift_metric = "KL",
    auuc_type = "qini",
    auuc_nbins = -1,
    mtries = -1) {
  .gen_train("upliftdrf", x, y, training_frame, validation_frame, model_id,
             params = list(seed = seed, nfolds = nfolds, fold_assignment = fold_assignment, fold_column = fold_column, weights_column = weights_column, ignored_columns = ignored_columns, max_runtime_secs = max_runtime_secs, keep_cross_validation_predictions = keep_cross_validation_predictions, checkpoint = checkpoint, auto_recovery_dir = auto_recovery_dir, custom_metric_func = custom_metric_func, ntrees = ntrees, max_depth = max_depth, min_rows = min_rows, nbins = nbins, sample_rate = sample_rate, col_sample_rate_per_tree = col_sample_rate_per_tree, min_split_improvement = min_split_improvement, stopping_rounds = stopping_rounds, stopping_metric = stopping_metric, stopping_tolerance = stopping_tolerance, score_tree_interval = score_tree_interval, score_each_iteration = score_each_iteration, monotone_constraints = monotone_constraints, interaction_constraints = interaction_constraints, calibrate_model = calibrate_model, calibration_frame = calibration_frame, calibration_method = calibration_method, nbins_cats = nbins_cats, categorical_encoding = categorical_encoding, offset_column = offset_column, treatment_column = treatment_column, uplift_metric = uplift_metric, auuc_type = auuc_type, auuc_nbins = auuc_nbins, mtries = mtries),
             defaults = list(seed = -1, nfolds = 0, fold_assignment = "Modulo", max_runtime_secs = 0.0, keep_cross_validation_predictions = 0, ntrees = 50, max_depth = 5, min_rows = 10.0, nbins = 64, sample_rate = 0.632, col_sample_rate_per_tree = 1.0, min_split_improvement = 1e-05, stopping_rounds = 0, stopping_metric = "AUTO", stopping_tolerance = 0.001, score_tree_interval = 0, score_each_iteration = 0, calibrate_model = 0, calibration_method = "PlattScaling", nbins_cats = 1024, categorical_encoding = "AUTO", uplift_metric = "KL", auuc_type = "qini", auuc_nbins = -1, mtries = -1))
}


# POST /3/ModelBuilders/word2vec — full server parameter surface
h2o.word2vec <- function(training_frame, x = NULL, y = NULL,
    validation_frame = NULL,
    model_id = NULL,
    seed = -1,
    nfolds = 0,
    fold_assignment = "Modulo",
    fold_column = NULL,
    weights_column = NULL,
    ignored_columns = NULL,
    max_runtime_secs = 0.0,
    keep_cross_validation_predictions = 0,
    checkpoint = NULL,
    auto_recovery_dir = NULL,
    custom_metric_func = NULL,
    vec_size = 100,
    window_size = 5,
    min_word_freq = 5,
    init_learning_rate = 0.025,
    sent_sample_rate = 0.001,
    epochs = 5,
    negative_samples = 5,
    mini_batch_size = 1024,
    word_model = "SkipGram",
    objective = "sgns",
    pre_trained = NULL) {
  .gen_train("word2vec", x, y, training_frame, validation_frame, model_id,
             params = list(seed = seed, nfolds = nfolds, fold_assignment = fold_assignment, fold_column = fold_column, weights_column = weights_column, ignored_columns = ignored_columns, max_runtime_secs = max_runtime_secs, keep_cross_validation_predictions = keep_cross_validation_predictions, checkpoint = checkpoint, auto_recovery_dir = auto_recovery_dir, custom_metric_func = custom_metric_func, vec_size = vec_size, window_size = window_size, min_word_freq = min_word_freq, init_learning_rate = init_learning_rate, sent_sample_rate = sent_sample_rate, epochs = epochs, negative_samples = negative_samples, mini_batch_size = mini_batch_size, word_model = word_model, objective = objective, pre_trained = pre_trained),
             defaults = list(seed = -1, nfolds = 0, fold_assignment = "Modulo", max_runtime_secs = 0.0, keep_cross_validation_predictions = 0, vec_size = 100, window_size = 5, min_word_freq = 5, init_learning_rate = 0.025, sent_sample_rate = 0.001, epochs = 5, negative_samples = 5, mini_batch_size = 1024, word_model = "SkipGram", objective = "sgns"))
}


# POST /3/ModelBuilders/xgboost — full server parameter surface
h2o.xgboost <- function(x = NULL, y = NULL, training_frame,
    validation_frame = NULL,
    model_id = NULL,
    seed = -1,
    nfolds = 0,
    fold_assignment = "Modulo",
    fold_column = NULL,
    weights_column = NULL,
    ignored_columns = NULL,
    max_runtime_secs = 0.0,
    keep_cross_validation_predictions = 0,
    checkpoint = NULL,
    auto_recovery_dir = NULL,
    custom_metric_func = NULL,
    ntrees = 50,
    max_depth = 6,
    min_rows = 1.0,
    nbins = 256,
    sample_rate = 1.0,
    col_sample_rate_per_tree = 1.0,
    min_split_improvement = 1e-05,
    stopping_rounds = 0,
    stopping_metric = "AUTO",
    stopping_tolerance = 0.001,
    score_tree_interval = 0,
    score_each_iteration = 0,
    monotone_constraints = NULL,
    interaction_constraints = NULL,
    calibrate_model = 0,
    calibration_frame = NULL,
    calibration_method = "PlattScaling",
    nbins_cats = 1024,
    categorical_encoding = "AUTO",
    offset_column = NULL,
    learn_rate = 0.3,
    distribution = "AUTO",
    reg_lambda = 1.0,
    col_sample_rate = 1.0,
    quantile_alpha = 0.5,
    huber_alpha = 0.9,
    tweedie_power = 1.5,
    custom_distribution_func = NULL,
    trees_per_dispatch = 0,
    reg_alpha = 0.0,
    gamma = 0.0,
    col_sample_by_node = 1.0,
    booster = "gbtree",
    rate_drop = 0.0,
    skip_drop = 0.0,
    one_drop = 0,
    normalize_type = "tree") {
  .gen_train("xgboost", x, y, training_frame, validation_frame, model_id,
             params = list(seed = seed, nfolds = nfolds, fold_assignment = fold_assignment, fold_column = fold_column, weights_column = weights_column, ignored_columns = ignored_columns, max_runtime_secs = max_runtime_secs, keep_cross_validation_predictions = keep_cross_validation_predictions, checkpoint = checkpoint, auto_recovery_dir = auto_recovery_dir, custom_metric_func = custom_metric_func, ntrees = ntrees, max_depth = max_depth, min_rows = min_rows, nbins = nbins, sample_rate = sample_rate, col_sample_rate_per_tree = col_sample_rate_per_tree, min_split_improvement = min_split_improvement, stopping_rounds = stopping_rounds, stopping_metric = stopping_metric, stopping_tolerance = stopping_tolerance, score_tree_interval = score_tree_interval, score_each_iteration = score_each_iteration, monotone_constraints = monotone_constraints, interaction_constraints = interaction_constraints, calibrate_model = calibrate_model, calibration_frame = calibration_frame, calibration_method = calibration_method, nbins_cats = nbins_cats, categorical_encoding = categorical_encoding, offset_column = offset_column, learn_rate = learn_rate, distribution = distribution, reg_lambda = reg_lambda, col_sample_rate = col_sample_rate, quantile_alpha = quantile_alpha, huber_alpha = huber_alpha, tweedie_power = tweedie_power, custom_distribution_func = custom_distribution_func, trees_per_dispatch = trees_per_dispatch, reg_alpha = reg_alpha, gamma = gamma, col_sample_by_node = col_sample_by_node, booster = booster, rate_drop = rate_drop, skip_drop = skip_drop, one_drop = one_drop, normalize_type = normalize_type),
             defaults = list(seed = -1, nfolds = 0, fold_assignment = "Modulo", max_runtime_secs = 0.0, keep_cross_validation_predictions = 0, ntrees = 50, max_depth = 6, min_rows = 1.0, nbins = 256, sample_rate = 1.0, col_sample_rate_per_tree = 1.0, min_split_improvement = 1e-05, stopping_rounds = 0, stopping_metric = "AUTO", stopping_tolerance = 0.001, score_tree_interval = 0, score_each_iteration = 0, calibrate_model = 0, calibration_method = "PlattScaling", nbins_cats = 1024, categorical_encoding = "AUTO", learn_rate = 0.3, distribution = "AUTO", reg_lambda = 1.0, col_sample_rate = 1.0, quantile_alpha = 0.5, huber_alpha = 0.9, tweedie_power = 1.5, trees_per_dispatch = 0, reg_alpha = 0.0, gamma = 0.0, col_sample_by_node = 1.0, booster = "gbtree", rate_drop = 0.0, skip_drop = 0.0, one_drop = 0, normalize_type = "tree"))
}
