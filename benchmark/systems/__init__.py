"""The system under test, one adaptor a model: how the benchmark builds the
program's own objects (``TrainStep``, ``GenerationEngine``,
``ContinuousBatcher``) from a configuration file and hands them the
benchmark's weights. The only files of the benchmark that import the
program."""
