module @jit_f attributes {mhlo.num_partitions = 8 : i32, mhlo.num_replicas = 1 : i32} {
  func.func public @main(%arg0: tensor<4xf32> {mhlo.sharding = "{replicated}", tf.aliasing_output = 0 : i32}, %arg1: tensor<8x4xf32> {mhlo.sharding = "{devices=[8,1]<=[8]}"}, %arg2: tensor<4xf32> {jax.buffer_donor = true, mhlo.sharding = "{devices=[4,2]<=[2,4]T(1,0) last_tile_dim_replicate}"}) -> (tensor<4xf32> {jax.result_info = "result", mhlo.sharding = "{devices=[4,2]<=[2,4]T(1,0) last_tile_dim_replicate}"}) {
    %cst = stablehlo.constant dense<2.000000e+00> : tensor<f32>
    %0 = stablehlo.broadcast_in_dim %cst, dims = [] : (tensor<f32>) -> tensor<8x4xf32>
    %1 = stablehlo.multiply %arg1, %0 : tensor<8x4xf32>
    %2 = stablehlo.custom_call @Sharding(%1) {backend_config = "", mhlo.sharding = "{devices=[4,1,2]<=[2,4]T(1,0) last_tile_dim_replicate}"} : (tensor<8x4xf32>) -> tensor<8x4xf32>
    %cst_0 = stablehlo.constant dense<0.000000e+00> : tensor<f32>
    %3 = stablehlo.reduce(%2 init: %cst_0) applies stablehlo.add across dimensions = [0, 1] : (tensor<8x4xf32>, tensor<f32>) -> tensor<f32>
    %4 = stablehlo.broadcast_in_dim %3, dims = [] : (tensor<f32>) -> tensor<4xf32>
    %5 = stablehlo.add %arg0, %4 : tensor<4xf32>
    return %5 : tensor<4xf32>
  }
}
