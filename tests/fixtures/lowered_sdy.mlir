module @jit_f attributes {mhlo.num_partitions = 8 : i32, mhlo.num_replicas = 1 : i32} {
  sdy.mesh @mesh = <["dp"=2, "fsdp"=4]>
  func.func public @main(%arg0: tensor<4xf32> {sdy.sharding = #sdy.sharding<@mesh, [{}]>, tf.aliasing_output = 0 : i32}, %arg1: tensor<8x4xf32> {sdy.sharding = #sdy.sharding<@mesh, [{"dp", "fsdp"}, {}]>}, %arg2: tensor<4xf32> {jax.buffer_donor = true, sdy.sharding = #sdy.sharding<@mesh, [{"fsdp"}]>}) -> (tensor<4xf32> {jax.result_info = "result", sdy.sharding = #sdy.sharding<@mesh, [{"fsdp"}]>}) {
    %cst = stablehlo.constant dense<2.000000e+00> : tensor<f32>
    %0 = stablehlo.broadcast_in_dim %cst, dims = [] : (tensor<f32>) -> tensor<8x4xf32>
    %1 = stablehlo.multiply %arg1, %0 : tensor<8x4xf32>
    %2 = sdy.sharding_constraint %1 <@mesh, [{"fsdp"}, {}]> : tensor<8x4xf32>
    %cst_0 = stablehlo.constant dense<0.000000e+00> : tensor<f32>
    %3 = stablehlo.reduce(%2 init: %cst_0) applies stablehlo.add across dimensions = [0, 1] : (tensor<8x4xf32>, tensor<f32>) -> tensor<f32>
    %4 = stablehlo.broadcast_in_dim %3, dims = [] : (tensor<f32>) -> tensor<4xf32>
    %5 = stablehlo.add %arg0, %4 : tensor<4xf32>
    return %5 : tensor<4xf32>
  }
}
